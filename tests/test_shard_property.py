"""Property tests for the deterministic reader shard map
(cxxnet_tpu/io/shard.py) — the multi-host input invariants:

- **exactly-once**: every record index is owned by exactly one host,
  at any (world size, global batch, dataset size) — no duplicated and
  no dropped data fleet-wide.
- **bit-identical assembly**: concatenating the hosts' owned indices
  in rank order reconstructs the exact single-host record order.
- **elastic no-dup/no-loss**: a resize at an update boundary
  (``ShardPlan.rederive``) splits the stream cleanly — records before
  the handoff were consumed exactly once by the old plans, records
  after it are owned exactly once by the new plans.

Exhaustive small-grid sweeps instead of a hypothesis dependency (the
container must not grow packages); the grid covers every divisor
world size, non-dividing dataset sizes, and every batch-boundary
resize point.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from cxxnet_tpu.io.shard import ShardPlan, shard_owner


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


@pytest.mark.parametrize("B", [4, 6, 8, 12])
def test_every_record_owned_exactly_once_any_world_size(B):
    for H in _divisors(B):
        plans = [ShardPlan(h, H, B) for h in range(H)]
        for N in (0, 1, B - 1, B, B + 3, 3 * B + 1, 5 * B):
            for i in range(N):
                owners = [h for h, p in enumerate(plans)
                          if p.owns(i)]
                assert owners == [shard_owner(i, B, H)], \
                    "record %d (B=%d H=%d) owned by %r" \
                    % (i, B, H, owners)


def test_rank_order_concat_reconstructs_global_order():
    """Within every global batch, host h's slice is the h-th
    contiguous block — concatenation in rank order IS the single-host
    order (the dryrun bit-identity invariant at the index level)."""
    B, H = 12, 3
    plans = [ShardPlan(h, H, B) for h in range(H)]
    N = 5 * B
    for k in range(N // B):
        got = []
        for p in plans:
            lo, hi = p.slice_of_batch(k)
            owned = [i for i in range(k * B, (k + 1) * B) if p.owns(i)]
            assert owned == list(range(lo, hi))
            got.extend(owned)
        assert got == list(range(k * B, (k + 1) * B))


def test_resize_at_update_boundary_is_no_dup_no_loss():
    """Every (old world, new world, resize point) on the grid: the old
    plans own exactly [0, s) and the rederived plans exactly [s, N),
    disjointly — the elastic handoff invariant."""
    B = 12
    N = 6 * B
    for H_old in _divisors(B):
        old = [ShardPlan(h, H_old, B) for h in range(H_old)]
        for H_new in _divisors(B):
            for batches_consumed in range(N // B + 1):
                s = batches_consumed * B
                new = [old[0].rederive(h, H_new, batches_consumed)
                       for h in range(H_new)]
                consumed_old = sorted(
                    i for p in old for i in p.owned_indices(s))
                owned_new = sorted(
                    i for p in new for i in p.owned_indices(N))
                # no loss, no dup: old covers [0, s) once, new covers
                # [s, N) once, and they never overlap
                assert consumed_old == list(range(s))
                assert owned_new == list(range(s, N))


def test_plan_validation():
    with pytest.raises(ValueError):
        ShardPlan(0, 3, 8)               # 8 rows don't split 3 ways
    with pytest.raises(ValueError):
        ShardPlan(2, 2, 8)               # rank out of range
    with pytest.raises(ValueError):
        ShardPlan(0, 2, 8, start_record=3)   # not a batch boundary
    with pytest.raises(ValueError):
        ShardPlan(0, 2, 8, start_record=-8)


def test_csv_iterator_batch_shard_disjoint_union(tmp_path):
    """The CSV reader's shard_kind=batch path: per-host row sets are
    disjoint, union to the file, and each host's order is the global
    order restricted to its slices."""
    from cxxnet_tpu.io.iter_csv import CSVIterator
    path = str(tmp_path / "s.csv")
    n, B, H = 22, 8, 2
    with open(path, "w") as f:
        for i in range(n):
            f.write("%d,%d,%d\n" % (i % 3, i, i * 10))
    seen = {}
    for h in range(H):
        it = CSVIterator()
        for k, v in (("filename", path), ("input_shape", "1,1,2"),
                     ("silent", "1"), ("part_index", str(h)),
                     ("num_parts", str(H)), ("shard_kind", "batch"),
                     ("shard_global_batch", str(B))):
            it.set_param(k, v)
        it.init()
        got = []
        it.before_first()
        while it.next():
            got.append(it.value().index)
        seen[h] = got
        plan = ShardPlan(h, H, B)
        assert got == plan.owned_indices(n)
    all_idx = sorted(seen[0] + seen[1])
    assert all_idx == list(range(n))
    assert not set(seen[0]) & set(seen[1])


def test_csv_iterator_batch_shard_start_record(tmp_path):
    """shard_start_record skips the records a previous plan consumed
    (the mid-stream elastic handoff knob) on the RESUMED pass only —
    every later epoch reads the full shard again (a permanent skip
    would silently train without the dataset's head forever)."""
    from cxxnet_tpu.io.iter_csv import CSVIterator
    path = str(tmp_path / "s.csv")
    n, B, H, start = 24, 8, 2, 8
    with open(path, "w") as f:
        for i in range(n):
            f.write("%d,%d,%d\n" % (i % 3, i, i * 10))
    first, second = [], []
    for h in range(H):
        it = CSVIterator()
        for k, v in (("filename", path), ("input_shape", "1,1,2"),
                     ("silent", "1"), ("part_index", str(h)),
                     ("num_parts", str(H)), ("shard_kind", "batch"),
                     ("shard_global_batch", str(B)),
                     ("shard_start_record", str(start))):
            it.set_param(k, v)
        it.init()
        it.before_first()                # adapter-init style reset:
        it.before_first()                # must NOT clear the offset
        while it.next():
            first.append(it.value().index)
        it.before_first()                # pass complete -> steady plan
        while it.next():
            second.append(it.value().index)
    assert sorted(first) == list(range(start, n))
    assert sorted(second) == list(range(n))


def test_imgrec_batch_shard_start_record_first_pass_only(tmp_path):
    from cxxnet_tpu.io.iter_imgrec import ImageRecordIterator
    from cxxnet_tpu.io.recordio import (RecordIOWriter,
                                        pack_raw_tensor_record)
    path = str(tmp_path / "s.rec")
    n, B, start = 18, 6, 6
    rng = np.random.RandomState(0)
    w = RecordIOWriter(path, force_python=True)
    for i in range(n):
        img = rng.randint(0, 255, (4, 4, 3), np.uint8)
        w.write_record(pack_raw_tensor_record(i, float(i % 3), img))
    w.close()
    it = ImageRecordIterator()
    for k, v in (("path_imgrec", path), ("silent", "1"),
                 ("part_index", "0"), ("num_parts", "1"),
                 ("shard_kind", "batch"),
                 ("shard_global_batch", str(B)),
                 ("shard_start_record", str(start))):
        it.set_param(k, v)
    it.init()
    it.before_first()
    first = [int(it.value().index) for _ in iter(it.next, False)]
    it.before_first()
    second = [int(it.value().index) for _ in iter(it.next, False)]
    it.close()
    assert first == list(range(start, n))
    assert second == list(range(n))


def test_imgrec_batch_shard_decodes_only_owned(tmp_path):
    """The RecordIO reader's shard_kind=batch path over raw tensor
    records (no jpeg): per-host record sets are disjoint, union to
    the archive, order preserved."""
    from cxxnet_tpu.io.iter_imgrec import ImageRecordIterator
    from cxxnet_tpu.io.recordio import (RecordIOWriter,
                                        pack_raw_tensor_record)
    path = str(tmp_path / "s.rec")
    n, B, H = 19, 6, 3
    rng = np.random.RandomState(0)
    w = RecordIOWriter(path, force_python=True)
    for i in range(n):
        img = rng.randint(0, 255, (4, 4, 3), np.uint8)
        w.write_record(pack_raw_tensor_record(i, float(i % 3), img))
    w.close()
    seen = {}
    for h in range(H):
        it = ImageRecordIterator()
        for k, v in (("path_imgrec", path), ("silent", "1"),
                     ("part_index", str(h)), ("num_parts", str(H)),
                     ("shard_kind", "batch"),
                     ("shard_global_batch", str(B))):
            it.set_param(k, v)
        it.init()
        got = []
        it.before_first()
        while it.next():
            got.append(int(it.value().index))
        it.close()
        seen[h] = got
        assert got == ShardPlan(h, H, B).owned_indices(n)
    union = sorted(sum(seen.values(), []))
    assert union == list(range(n))
