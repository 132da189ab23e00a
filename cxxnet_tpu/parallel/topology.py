"""Host topology: the (hosts x local-devices) shape the mesh and the
input shard map derive from — and the single-process *dryrun* that
fakes it.

The reference scales across machines through rabit/ps-lite workers
(SURVEY.md §2.7, example/multi-machine/run.sh); the TPU-native
equivalent is one SPMD program over a mesh whose **data axis spans
hosts x local devices** while the **model axis stays within a host**
(collectives on the model axis run every layer — they belong on ICI,
never on DCN). This module owns that topology decision:

- :func:`current_topology` — the (num_hosts, host_rank, local devices)
  triple, read from ``jax`` for real multi-process runs or from the
  faked dryrun state below.
- :func:`set_dryrun_topology` / :func:`clear_dryrun_topology` — the
  single-process multi-host **dryrun**: ``dist_dryrun_hosts = H``
  partitions the input pipeline into H virtual hosts (each reading
  only its deterministic record shard and producing only its slice of
  the global batch) while the device mesh stays the process's real
  devices. The full shard math — mesh build, per-host batch assembly,
  shard-map re-derivation — runs in tier-1 with zero recompiles and a
  loss trajectory bit-identical to the single-host run on the same
  global batch, because the assembled global batch IS the single-host
  batch (doc/distributed.md "Dryrun vs real").
- :class:`DryrunFeed` — the dryrun batch assembler: one batch-level
  iterator chain per virtual host, concatenated in host-rank order —
  exactly the row order ``jax.make_array_from_process_local_data``
  gives a real multi-host run (each process's local rows land in
  ascending process order), so the dryrun validates the real
  assembly's data order, not a lookalike.

What the dryrun deliberately does NOT fake: cross-process collectives
(there is one process), DCN transport, per-host clock skew. Scaling
numbers from a dryrun measure shard math and input cost, never
interconnect — MULTICHIP records say so (the r07/r08 convention).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..io.data import DataBatch, IIterator

# faked host count installed by set_dryrun_topology (0 = real topology)
_dryrun_hosts = 0


class HostTopology:
    """The (hosts, local devices) shape of the fleet.

    ``num_hosts``/``host_rank`` are the INPUT topology — what the
    reader shard map partitions over. For a real multi-process run
    they equal ``jax.process_count()``/``process_index()``; under the
    dryrun they are the faked host count (rank is meaningless: one
    process drives every virtual host). ``local_device_count`` is the
    per-host device count the model axis must stay within.
    """

    __slots__ = ("num_hosts", "host_rank", "local_device_count",
                 "dryrun")

    def __init__(self, num_hosts: int, host_rank: int,
                 local_device_count: int, dryrun: bool = False):
        self.num_hosts = int(num_hosts)
        self.host_rank = int(host_rank)
        self.local_device_count = int(local_device_count)
        self.dryrun = bool(dryrun)

    @property
    def world_devices(self) -> int:
        return self.num_hosts * self.local_device_count

    def describe(self) -> Dict[str, Any]:
        """Telemetry/snapshot-meta form (``dist_topology`` record and
        the snapshot ``topology`` entry both carry this)."""
        return {"hosts": self.num_hosts,
                "local_devices": self.local_device_count,
                "world_devices": self.world_devices,
                "dryrun": self.dryrun}


def set_dryrun_topology(num_hosts: int) -> HostTopology:
    """Install the faked multi-host topology: ``num_hosts`` virtual
    hosts partitioning this single process's devices. Requires a
    single-process runtime (a real multi-process run already HAS a
    topology) and a host count that divides the device count (each
    virtual host owns an equal local slice). Returns the topology;
    callers must :func:`clear_dryrun_topology` when done — main.py
    clears in its task ``finally`` so library users never inherit a
    stale fake."""
    global _dryrun_hosts
    import jax
    assert jax.process_count() == 1, \
        "dist_dryrun_hosts fakes a topology; a real multi-process " \
        "run already has one"
    ndev = len(jax.devices())
    n = int(num_hosts)
    if n < 1 or ndev % n != 0:
        raise ValueError(
            "dist_dryrun_hosts=%d must divide the %d available "
            "devices (each virtual host owns an equal local slice)"
            % (n, ndev))
    _dryrun_hosts = n
    return current_topology()


def clear_dryrun_topology() -> None:
    global _dryrun_hosts
    _dryrun_hosts = 0


def current_topology() -> HostTopology:
    """The active topology: faked when a dryrun is installed, else the
    real jax process topology."""
    import jax
    if _dryrun_hosts > 1:
        return HostTopology(_dryrun_hosts, 0,
                            len(jax.devices()) // _dryrun_hosts,
                            dryrun=True)
    return HostTopology(jax.process_count(), jax.process_index(),
                        len(jax.local_devices()))


# -- the dryrun batch assembler -------------------------------------------


class DryrunFeed(IIterator):
    """Assemble global batches from one batch-level iterator per
    virtual host, concatenated in host-rank order.

    Mirrors ``jax.make_array_from_process_local_data`` row order: the
    global batch's rows are host 0's local rows, then host 1's, ...
    With the batch-block shard map (:mod:`cxxnet_tpu.io.shard`) each
    host's slice is exactly its contiguous span of the single-host
    batch, so the assembled batch is BIT-IDENTICAL to the unsharded
    read — the dryrun's headline invariant.

    Per-host accounting rides along: real (non-padded) rows consumed
    per host and the wall time spent blocked on each host's chain
    (the per-host data-wait of the scaling record). Padding must form
    a suffix of the global batch (real rows fill positions in record
    order under the batch-block map); the assembler asserts it rather
    than silently mis-masking.
    """

    def __init__(self, host_iters: Sequence[IIterator]):
        assert len(host_iters) >= 1
        self.hosts: List[IIterator] = list(host_iters)
        self._out: Optional[DataBatch] = None
        self.rows_per_host = [0] * len(self.hosts)
        self.wait_s_per_host = [0.0] * len(self.hosts)
        self.batches = 0
        # last batch each host produced: the shape template for the
        # all-padding slice an exhausted high-rank host contributes
        # while lower ranks still hold the dataset's real tail
        self._template: List[Optional[DataBatch]] = \
            [None] * len(self.hosts)

    # set_param is deliberately absent from forwarding: the per-host
    # chains are fully configured by build_dryrun_feed before assembly

    def init(self) -> None:
        for it in self.hosts:
            it.init()

    def before_first(self) -> None:
        for it in self.hosts:
            it.before_first()

    def next(self) -> bool:
        got: List[Optional[DataBatch]] = []
        any_live = False
        for h, it in enumerate(self.hosts):
            t0 = time.perf_counter()
            ok = it.next()
            self.wait_s_per_host[h] += time.perf_counter() - t0
            if ok:
                b = it.value()
                self._template[h] = b
                got.append(b)
                any_live = True
            else:
                got.append(None)
        if not any_live:
            return False
        # a dataset whose size is not a batch multiple leaves the
        # final global batch's high-position slices empty: those
        # hosts' chains exhaust one batch early, but the fleet must
        # still dispatch the batch in lockstep (a real rank does —
        # every rank pads; see trainer._mask). Exhausted hosts
        # contribute an all-padding slice shaped like their last
        # batch. The batch-block map guarantees only HIGH ranks can
        # exhaust early (real records fill positions in order), so a
        # live host after an exhausted one is a shard-config bug.
        parts: List[DataBatch] = []
        seen_dead = False
        for h, b in enumerate(got):
            if b is None:
                if not seen_dead and any(x is not None
                                         for x in got[h + 1:]):
                    raise AssertionError(
                        "dryrun host %d exhausted while a later host "
                        "still produces — the batch-block shard map "
                        "never does this (foreign shard config?)" % h)
                seen_dead = True
                tpl = self._template[h]
                if tpl is None:
                    # this host never owned a single record (dataset
                    # smaller than its first slice): borrow any live
                    # host's shapes — all local slices are equal-sized
                    tpl = next(x for x in got if x is not None)
                parts.append(DataBatch(
                    data=np.zeros_like(np.asarray(tpl.data)),
                    label=np.zeros_like(np.asarray(tpl.label)),
                    inst_index=None if tpl.inst_index is None
                    else np.zeros_like(np.asarray(tpl.inst_index)),
                    num_batch_padd=np.asarray(tpl.data).shape[0],
                    extra_data=[np.zeros_like(np.asarray(e))
                                for e in tpl.extra_data]))
            else:
                parts.append(b)
        padd = 0
        for h, b in enumerate(parts):
            real = b.batch_size - b.num_batch_padd
            if padd and real:
                raise AssertionError(
                    "dryrun host %d contributes %d real rows after an "
                    "earlier host padded — per-host padding must form "
                    "a suffix of the global batch (is round_batch=0 "
                    "and shuffle off on every host chain?)" % (h, real))
            padd += b.num_batch_padd
            self.rows_per_host[h] += real
        idx = None
        if all(b.inst_index is not None for b in parts):
            idx = np.concatenate([np.asarray(b.inst_index)
                                  for b in parts])
        n_extra = len(parts[0].extra_data)
        self._out = DataBatch(
            data=np.concatenate([np.asarray(b.data) for b in parts]),
            label=np.concatenate([np.asarray(b.label) for b in parts]),
            inst_index=idx,
            num_batch_padd=padd,
            extra_data=[np.concatenate(
                [np.asarray(b.extra_data[j]) for b in parts])
                for j in range(n_extra)])
        # the concatenates above copied out of any ring buffers; hand
        # the per-host leases back so each chain can reuse its buffers
        for b in parts:
            if b.release is not None:
                b.release()
        self.batches += 1
        return True

    def value(self) -> DataBatch:
        return self._out

    def close(self) -> None:
        for it in self.hosts:
            it.close()

    def accounting(self) -> Dict[str, Any]:
        """Per-host input-shard accounting since construction — the
        ``dist_shard`` record fields and the MULTICHIP
        records-consumed-per-host column (sums exactly to the real
        rows of the dataset per epoch)."""
        return {"hosts": len(self.hosts),
                "rows_per_host": list(self.rows_per_host),
                "wait_ms_per_host": [round(w * 1e3, 3)
                                     for w in self.wait_s_per_host],
                "batches": self.batches}

    def reset_accounting(self) -> None:
        self.rows_per_host = [0] * len(self.hosts)
        self.wait_s_per_host = [0.0] * len(self.hosts)
        self.batches = 0


def localize_block(pairs, hosts: int):
    """Divide every ``batch_size`` in an iterator block's config by the
    host count — each virtual host's chain produces its 1/hosts slice
    of the GLOBAL batch, the same rule main.py applies per process
    under real multi-process dp."""
    if hosts == 1:
        return list(pairs)
    out = []
    for k, v in pairs:
        if k == "batch_size":
            assert int(v) % hosts == 0, \
                "batch_size %s must divide evenly across %d hosts" \
                % (v, hosts)
            v = str(int(v) // hosts)
        out.append((k, v))
    return out


# knobs neutralized on every per-host dryrun chain: the bit-identity
# and exactly-once invariants need deterministic record order (no
# shuffle) and zero-padded tails (round_batch=1 wraps the tail with
# epoch-start records, which would double-count them in the shard
# accounting)
DRYRUN_NEUTRAL = (("shuffle", "0"), ("shuffle_chunk", "0"),
                  ("round_batch", "0"))


def build_dryrun_feed(block_cfg, batch_cfg, hosts: int,
                      global_batch: int,
                      start_record: int = 0) -> DryrunFeed:
    """Build the H per-host iterator chains + assembler for one data
    block — the ONE construction main.py's train path and the dryrun
    scaling sweep share, so the tested path is the shipped path.

    Each host chain gets the deterministic batch-block shard params
    (``shard_kind = batch``: host h owns rows [h*b, (h+1)*b) of every
    global batch — :mod:`cxxnet_tpu.io.shard`), its 1/H local
    batch_size, and the dryrun neutralizations (shuffle off,
    zero-padded tails)."""
    its = []
    for h in range(hosts):
        cfg_h = localize_block(block_cfg, hosts) + list(DRYRUN_NEUTRAL)
        cfg_h += [("shard_kind", "batch"),
                  ("part_index", str(h)),
                  ("num_parts", str(hosts)),
                  ("shard_global_batch", str(global_batch)),
                  ("shard_start_record", str(start_record))]
        from ..io import create_iterator
        its.append(create_iterator(cfg_h,
                                   localize_block(batch_cfg, hosts)))
    return DryrunFeed(its)
