"""imgrec's decode pool (io/iter_imgrec.py): a chunk goes to the pool in
contiguous slices and one chunk is decoded ahead of the consumer. The
sequence of DataInst must be that of a loop that decodes one record
after the other; restart and close must leave nothing of an old pass
behind; the pool follows the CPUs the process may use; the ``pipeline``
record and the ``io.decode`` span say what the stage did."""

import math
import os
import threading
import time
import zlib

import numpy as np
import pytest

from cxxnet_tpu.io import iter_imgrec
from cxxnet_tpu.io.iter_imgrec import ImageRecordIterator
from cxxnet_tpu.io.recordio import (RAW_TENSOR_FLAG, RecordIOReader,
                                    RecordIOWriter, pack_image_record,
                                    pack_raw_tensor_record,
                                    parse_image_record, record_flag,
                                    unpack_raw_tensor_record)
from cxxnet_tpu.io.shard import plan_from_params

CHUNK = 256


def _archive(path, n, kind="jpeg", bad=()):
    """``n`` records of 12 x 10 images; ``bad`` indices hold bytes no
    decoder takes."""
    import cv2
    rng = np.random.RandomState(11)
    w = RecordIOWriter(path)
    for i in range(n):
        img = rng.randint(0, 256, (12, 10, 3)).astype(np.uint8)
        label = float(i % 7)
        if i in bad:
            w.write_record(pack_image_record(i, label, b"not an image"))
        elif kind == "raw":
            w.write_record(pack_raw_tensor_record(i, label, img))
        else:
            ok, buf = cv2.imencode(".png", img)
            assert ok
            w.write_record(pack_image_record(i, label, buf.tobytes()))
    w.close()


def _decode_one(rec):
    """The reference's decode: one record, no pool."""
    import cv2
    if record_flag(rec) == RAW_TENSOR_FLAG:
        index, label, data = unpack_raw_tensor_record(rec)
        return index, label, data.astype(np.float32)
    index, label, _, payload = parse_image_record(rec)
    img = cv2.imdecode(np.frombuffer(payload, np.uint8), cv2.IMREAD_COLOR)
    if img is None:
        return None
    return index, label, img[:, :, ::-1].astype(np.float32)


def _reference(path, passes, shuffle=0, seed=0, shard=None, rng=None):
    """One record after the other: chunks of 256 records this host
    owns, the ones that fail to decode dropped, one shuffle a chunk."""
    rng = rng or np.random.RandomState(seed)
    plan = plan_from_params(*shard) if shard else None
    out = []
    for p in range(passes):
        if plan is not None and p > 0:
            plan = plan.steady()
        reader = RecordIOReader(path, 0, 1)
        chunk, taken, seq = [], 0, 0

        def hand_out():
            if shuffle:
                rng.shuffle(chunk)
            out.extend(chunk)
            del chunk[:]

        while True:
            rec = reader.next_record()
            if rec is None:
                break
            if plan is not None:
                owned = plan.owns(seq)
                seq += 1
                if not owned:
                    continue
            inst = _decode_one(rec)
            taken += 1
            if inst is not None:
                chunk.append(inst)
            if taken == CHUNK:
                hand_out()
                taken = 0
        if taken:
            hand_out()
        reader.close()
    return [(i, lab, zlib.crc32(px.tobytes())) for i, lab, px in out]


def _make(path, **params):
    it = ImageRecordIterator()
    it.set_param("path_imgrec", path)
    it.set_param("silent", "1")
    for k, v in params.items():
        it.set_param(k, str(v))
    it.init()
    return it


def _drain(it):
    out = []
    while it.next():
        v = it.value()
        assert v.label.shape == (1,) and v.data.dtype == np.float32
        out.append((int(v.index), float(v.label[0]),
                    zlib.crc32(np.ascontiguousarray(v.data).tobytes())))
    return out


def _two_passes(it):
    got = []
    for _ in range(2):
        it.before_first()
        got += _drain(it)
    it.close()
    return got


SHARD = (1, 3, 12, 24)      # host 1 of 3, global batch 12, resumed at 24

CASES = {
    "in_order": dict(n=600),
    "shuffle": dict(n=600, params=dict(shuffle=1, seed_data=5),
                    ref=dict(shuffle=1, seed=5)),
    "sharded": dict(n=900, params=dict(
        shard_kind="batch", part_index=SHARD[0], num_parts=SHARD[1],
        shard_global_batch=SHARD[2], shard_start_record=SHARD[3]),
        ref=dict(shard=SHARD)),
    "sharded_shuffle": dict(n=900, params=dict(
        shard_kind="batch", part_index=SHARD[0], num_parts=SHARD[1],
        shard_global_batch=SHARD[2], shard_start_record=SHARD[3],
        shuffle=1, seed_data=2), ref=dict(shard=SHARD, shuffle=1, seed=2)),
    "short_last_chunk": dict(n=CHUNK + 3),
    "whole_chunks": dict(n=2 * CHUNK),
    "less_than_a_slice_each": dict(n=3, params=dict(nthread=8)),
    "bad_records": dict(n=540, bad=(0, 7, 255, 256, 300, 539),
                        params=dict(shuffle=1, seed_data=1),
                        ref=dict(shuffle=1, seed=1)),
    "a_chunk_of_bad_records": dict(n=CHUNK + 40, bad=tuple(range(CHUNK))),
    "raw_tensors": dict(n=530, kind="raw"),
    "one_thread": dict(n=530, params=dict(nthread=1, shuffle=1),
                       ref=dict(shuffle=1)),
    "three_threads": dict(n=530, params=dict(nthread=3)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_sequence_is_a_one_record_loops(tmp_path, case):
    c = CASES[case]
    path = str(tmp_path / "a.rec")
    _archive(path, c["n"], c.get("kind", "jpeg"), c.get("bad", ()))
    want = _reference(path, 2, **c.get("ref", {}))
    got = _two_passes(_make(path, **c.get("params", {})))
    assert len(want) > 0 and got == want


def test_uint8_pixels_take_the_same_path(tmp_path):
    """``emit_uint8`` (the benchmark's chain) changes the dtype, not
    the order."""
    path = str(tmp_path / "a.rec")
    _archive(path, 300)
    it = _make(path)
    it.emit_uint8 = True
    it.before_first()
    got = []
    while it.next():
        v = it.value()
        assert v.data.dtype == np.uint8 and v.data.flags.c_contiguous
        got.append((int(v.index), v.data.astype(np.float32)))
    it.close()
    want = [_decode_one(r) for r in RecordIOReader(path, 0, 1)]
    assert [g[0] for g in got] == [w[0] for w in want]
    assert all(np.array_equal(g[1], w[2]) for g, w in zip(got, want))


def _slow(it, seconds=0.01):
    """``seconds`` more a record, inside the workers."""
    fast = it._decode_slice

    def slow(recs):
        time.sleep(seconds * len(recs))
        return fast(recs)
    it._decode_slice = slow


def test_before_first_in_mid_pass_starts_the_new_pass_clean(tmp_path):
    path = str(tmp_path / "a.rec")
    _archive(path, 3 * CHUNK + 10)
    it = _make(path, shuffle=1, nthread=2)
    _slow(it, 0.002)
    for _ in range(100):
        assert it.next()
    old = [f for _, futs in it._ahead for f in futs]
    assert old and not all(f.done() for f in old)    # a chunk in flight
    it.before_first()
    assert not it._ahead
    assert all(f.done() for f in old)   # cancelled, or run to its end
    # one chunk was handed out, so one shuffle was made; the chunk in
    # flight was dropped unshuffled and the records start over
    rng = np.random.RandomState(0)
    rng.shuffle(list(range(CHUNK)))
    assert _drain(it) == _reference(path, 1, shuffle=1, rng=rng)
    it.close()


def test_before_first_in_mid_pass_serves_the_first_record_first(tmp_path):
    path = str(tmp_path / "a.rec")
    _archive(path, 2 * CHUNK + 10)
    want = _reference(path, 1)
    it = _make(path)
    for _ in range(CHUNK + 5):          # into the second chunk
        assert it.next()
    it.before_first()
    assert _drain(it) == want
    it.before_first()                   # and after a whole pass
    assert _drain(it) == want
    assert not it.next()                # a pass that ended stays ended
    assert not it._ahead                # and nothing was decoded past it
    it.close()


def test_close_with_chunks_in_flight_returns(tmp_path):
    path = str(tmp_path / "a.rec")
    _archive(path, 3 * CHUNK)
    it = _make(path, nthread=2)
    _slow(it, 0.005)
    assert it.next()
    assert it._ahead
    pool = it._pool
    done = threading.Event()

    def close():
        it.close()
        done.set()

    t0 = time.time()
    threading.Thread(target=close, daemon=True).start()
    assert done.wait(5.0), "close did not return"
    assert time.time() - t0 < 2.0       # it did not wait for 512 records
    assert not it._ahead and it._pool is None
    pool.shutdown(wait=True)            # the two running slices end
    it.close()                          # twice is fine


@pytest.mark.parametrize("cpus,pool", [(13, 6), (8, 4), (3, 4), (1, 4),
                                       (32, 16)])
def test_the_pool_follows_the_affinity_mask(tmp_path, monkeypatch, cpus,
                                            pool):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(cpus)), raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: 64)
    path = str(tmp_path / "a.rec")
    _archive(path, CHUNK + 20)
    it = _make(path)
    assert it.nthread == pool and it._pool._max_workers == pool
    assert it.next()
    # the chunk ahead (20 records) is in flight, in at most `pool` slices
    (n, futs), = it._ahead
    assert n == 20 and len(futs) == math.ceil(20 / math.ceil(20 / pool))
    snap = it.decode_snapshot()
    assert (snap["decode_pool"], snap["decode_cpus"], snap["cpu_count"]) \
        == (pool, cpus, 64)
    it.close()


def test_a_full_chunk_is_cut_into_a_slice_a_thread(tmp_path, monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(13)), raising=False)
    path = str(tmp_path / "a.rec")
    _archive(path, 2 * CHUNK)
    it = _make(path)
    sizes = []
    whole = it._decode_slice

    def spy(recs):
        sizes.append(len(recs))
        return whole(recs)
    it._decode_slice = spy
    assert len(_drain(it)) == 2 * CHUNK
    it.close()
    assert sorted(sizes) == sorted(2 * ([43] * 5 + [41]))


def test_without_an_affinity_mask_the_machines_count(monkeypatch):
    monkeypatch.delattr(os, "sched_getaffinity", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: 10)
    assert iter_imgrec.usable_cpus() == 10
    assert ImageRecordIterator().nthread == 5
    monkeypatch.setattr(os, "cpu_count", lambda: None)
    assert iter_imgrec.usable_cpus() == 1
    assert ImageRecordIterator().nthread == 4


def test_an_explicit_nthread_wins(tmp_path, monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(6)), raising=False)
    path = str(tmp_path / "a.rec")
    _archive(path, CHUNK)
    it = _make(path, nthread=2)
    assert it.nthread == 2 and it._pool._max_workers == 2
    assert it.next()
    snap = it.decode_snapshot()
    assert snap["decode_pool"] == 2 and snap["decode_cpus"] == 6
    it.close()


def _chain(path, mon=None, batch=64):
    from cxxnet_tpu.io import create_iterator
    from cxxnet_tpu.io.iter_batch import attach_chain_spans
    it = create_iterator(
        [("iter", "imgrec"), ("path_imgrec", path),
         ("input_shape", "3,8,8"), ("rand_crop", "1"), ("silent", "1"),
         ("iter", "threadbuffer")],
        [("batch_size", str(batch))])
    it.init()
    if mon is not None:
        attach_chain_spans(it, mon.span)
    return it


def test_the_pipeline_record_carries_the_decode_counters(tmp_path):
    from cxxnet_tpu.io.iter_batch import pipeline_snapshot
    from cxxnet_tpu.monitor.schema import OPTIONAL, validate_record
    path = str(tmp_path / "a.rec")
    _archive(path, 2 * CHUNK + 64)
    it = _chain(path)
    for rnd in range(2):
        it.before_first()
        assert sum(1 for _ in iter(it.next, False)) == 9
        snap = pipeline_snapshot(it)
        assert set(OPTIONAL["pipeline"]) <= set(snap)
        assert snap["decode_chunks"] == 3           # reset a round
        assert 0 <= snap["decode_ahead_ready"] <= snap["decode_chunks"]
        assert snap["decode_busy_ms"] > 0
        assert snap["decode_pool"] >= 4
        assert snap["decode_cpus"] == len(os.sched_getaffinity(0))
        assert snap["cpu_count"] == os.cpu_count()
        assert validate_record(dict(snap, event="pipeline", t=1.0,
                                    round=rnd)) == []
    it.close()


def test_a_chunk_the_consumer_left_alone_was_decoded_ahead(tmp_path):
    path = str(tmp_path / "a.rec")
    _archive(path, 2 * CHUNK)
    it = _make(path)
    _slow(it, 0.0005)                   # chunk 0 is waited for
    assert it.next()                    # chunk 0 handed out, 1 in flight
    (_, futs), = it._ahead
    for f in futs:
        f.result()
    for _ in range(CHUNK):
        assert it.next()                # into chunk 1
    snap = it.decode_snapshot()
    assert snap["decode_chunks"] == 2 and snap["decode_ahead_ready"] == 1
    it.close()


def test_a_chain_without_imgrec_has_no_decode_counters(tmp_path):
    from cxxnet_tpu.io import create_iterator
    from cxxnet_tpu.io.iter_batch import pipeline_snapshot
    csv = tmp_path / "a.csv"
    csv.write_text("".join("%d,%d,%d\n" % (i % 2, i, i + 1)
                           for i in range(8)))
    it = create_iterator(
        [("iter", "csv"), ("filename", str(csv)),
         ("input_shape", "1,1,2"), ("label_width", "1"), ("silent", "1")],
        [("batch_size", "4")])
    it.init()
    it.before_first()
    assert it.next()
    snap = pipeline_snapshot(it)
    assert snap is not None and "decode_chunks" not in snap
    it.close()


def test_decode_spans_stay_one_a_chunk_on_the_producer_thread(tmp_path):
    from cxxnet_tpu.monitor import MemorySink, Monitor
    path = str(tmp_path / "a.rec")
    _archive(path, 2 * CHUNK + 64)
    mon = Monitor(MemorySink())
    it = _chain(path, mon)
    it.before_first()
    assert sum(1 for _ in iter(it.next, False)) == 9
    it.close()
    mon.close()
    spans = [r for r in mon.sink.records if r["event"] == "span"]
    decode = [s for s in spans if s["name"] == "io.decode"]
    assert [s["attrs"] for s in decode] == [{"n": CHUNK}, {"n": CHUNK},
                                            {"n": 64}]
    reads = [s for s in spans if s["name"] == "io.read"]
    # the third read, of 64 records, finds the archive's end
    assert len(reads) == 3
    producer = {s["tid"] for s in spans if s["name"] == "io.assemble"}
    assert len(producer) == 1 and threading.get_ident() not in producer
    assert {s["tid"] for s in decode + reads} == producer
    # a chunk's records are read before the chunk ahead of it is
    # waited for: read 0, read 1, decode 0, read 2, decode 1, ...
    order = sorted(decode + reads, key=lambda s: s["t0_ns"])
    assert [s["name"] for s in order[:4]] == ["io.read", "io.read",
                                              "io.decode", "io.read"]


# -- the readers' bulk read --------------------------------------------------


def _payload_archive(path):
    import struct
    from cxxnet_tpu.io.recordio import KMAGIC
    rng = np.random.RandomState(3)
    magic = struct.pack("<I", KMAGIC)
    payloads = [rng.bytes(int(rng.randint(1, 3000))) for _ in range(300)]
    payloads[5:5] = [b"", magic, b"ab" + magic * 2 + b"c", b""]
    w = RecordIOWriter(path, force_python=True)
    for p in payloads:
        w.write_record(p)
    w.close()
    return payloads


@pytest.mark.parametrize("python", [True, False])
@pytest.mark.parametrize("part,parts", [(0, 1), (0, 3), (1, 3), (2, 3)])
def test_next_records_reads_what_a_record_at_a_time_reads(tmp_path, python,
                                                          part, parts):
    from cxxnet_tpu.io.recordio import native_available
    if not python and not native_available():
        pytest.skip("native lib not built")
    path = str(tmp_path / "p.rec")
    payloads = _payload_archive(path)
    one = RecordIOReader(path, part, parts, force_python=python)
    want = []
    while True:
        r = one.next_record()
        if r is None:
            break
        want.append(r)
    one.close()
    if parts == 1:
        assert want == payloads
    bulk = RecordIOReader(path, part, parts, force_python=python)
    for _ in range(2):                  # and again after a reset
        got, sizes = [], []
        for n in (1, 0, 7, 64, 1000, 5):
            recs = bulk.next_records(n)
            assert len(recs) <= n
            sizes.append(len(recs))
            got += recs
        assert got == want and all(type(r) is bytes for r in got)
        assert sizes[:3] == [1, 0, 7] and sizes[-1] == 0
        bulk.reset()
    # the two ways of reading move one cursor
    assert bulk.next_records(2) == want[:2]
    assert bulk.next_record() == want[2]
    assert bulk.next_records(1) == want[3:4]
    bulk.close()


def test_a_library_without_the_bulk_call_reads_a_record_at_a_time(
        tmp_path, monkeypatch):
    from cxxnet_tpu.io import recordio
    if not recordio.native_available():
        pytest.skip("native lib not built")
    path = str(tmp_path / "p.rec")
    payloads = _payload_archive(path)
    monkeypatch.setattr(recordio, "_has_next_n", False)
    r = RecordIOReader(path)
    assert type(r).__name__ == "_NativeReader"
    assert r.next_records(10) + r.next_records(1000) == payloads
    assert r.next_records(3) == []
    r.close()
