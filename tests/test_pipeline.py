"""Host-side hot-path optimizations: vectorized batch augmentation
(bit-identical to the per-instance path), zero-copy ring-buffer batch
assembly with ownership hand-off, condition-variable prefetch with
pipelined H2D staging, and AOT precompile.
"""

import time

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch, DataInst, IIterator
from cxxnet_tpu.io.iter_augment import AugmentAdapter
from cxxnet_tpu.io.iter_batch import (BatchAdapter, PrefetchIterator,
                                      _aligned_empty, pipeline_snapshot)
from tests.test_io import CountingIterator


class ImageSource(IIterator):
    """Serves n distinct random images (uint8 or float32)."""

    def __init__(self, n=37, size=24, dtype=np.uint8, seed=3):
        rng = np.random.RandomState(seed)
        if dtype == np.uint8:
            self.imgs = rng.randint(0, 256, (n, size, size, 3)) \
                .astype(np.uint8)
        else:
            self.imgs = (rng.rand(n, size, size, 3) * 255) \
                .astype(np.float32)
        self.n = n

    def init(self):
        self.i = 0

    def before_first(self):
        self.i = 0

    def next(self):
        if self.i >= self.n:
            return False
        self._v = DataInst(index=self.i + 7, data=self.imgs[self.i],
                           label=np.asarray([float(self.i % 5)]))
        self.i += 1
        return True

    def value(self):
        return self._v


def _aug_chain(params, vectorize, dtype=np.uint8, batch=8):
    ba = BatchAdapter(AugmentAdapter(ImageSource(dtype=dtype)))
    ba.set_param("batch_size", str(batch))
    ba.set_param("input_shape", "3,16,16")
    ba.set_param("augment_vectorize", str(vectorize))
    for k, v in params:
        ba.set_param(k, v)
    ba.init()
    return ba


KNOBSETS = [
    [],
    [("rand_crop", "1"), ("rand_mirror", "1")],
    [("rand_crop", "1"), ("rand_mirror", "1"), ("divideby", "256"),
     ("mean_value", "120,117,104")],
    [("mirror", "1"), ("scale", "0.017")],
    [("crop_y_start", "2"), ("crop_x_start", "5")],
]


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("knobs", KNOBSETS,
                         ids=["plain", "randcrop", "mean_scale",
                              "mirror_scale", "fixed_crop"])
def test_vectorized_augment_bit_identical(knobs, dtype):
    """The no-affine fast path produces BIT-identical batches to the
    per-instance path: same per-instance seeded RNG draws, same
    elementwise op order (the seeded-RNG parity criterion)."""
    vec = _aug_chain(knobs, 1, dtype)
    ref = _aug_chain(knobs, 0, dtype)
    assert vec._aug is not None, "fast path should be deferred"
    assert ref._aug is None
    va = [(b.data.copy(), b.label.copy(), b.inst_index.copy(),
           b.num_batch_padd) for b in vec]
    rb = [(b.data.copy(), b.label.copy(), b.inst_index.copy(),
           b.num_batch_padd) for b in ref]
    assert len(va) == len(rb) > 0
    for (dv, lv, iv, pv), (dr, lr, ir, pr) in zip(va, rb):
        assert dv.dtype == dr.dtype
        np.testing.assert_array_equal(dv, dr)
        np.testing.assert_array_equal(lv, lr)
        np.testing.assert_array_equal(iv, ir)
        assert pv == pr


@pytest.mark.parametrize("knobs", [
    [("max_rotate_angle", "30")],
    [("max_shear_ratio", "0.2")],
    [("min_crop_size", "8"), ("max_crop_size", "20")],
    [("max_random_contrast", "0.3")],
    [("max_random_illumination", "10")],
    [("min_random_scale", "0.8"), ("max_random_scale", "1.2"),
     ("min_img_size", "16")],
], ids=["rotate", "shear", "crop_size", "contrast", "illum", "scale"])
def test_affine_and_jitter_knobs_fall_back(knobs):
    """Affine/crop-resize/color-jitter knobs force the per-instance
    path — deferral must refuse, and batches still come out."""
    pytest.importorskip("cv2")
    ba = _aug_chain(knobs, 1)
    assert ba._aug is None, "deferred with a non-vectorizable knob"
    batches = list(ba)
    assert len(batches) > 0
    assert batches[0].data.shape[1:] == (16, 16, 3)


def test_augment_vectorize_0_forces_per_instance():
    ba = _aug_chain([], 0)
    assert ba._aug is None


@pytest.mark.parametrize("n", [11, 5])   # short tail / dataset < batch
def test_vectorized_parity_on_zero_padded_tail(n):
    """round_batch=0 zero-filler rows must stay EXACT zeros in the
    vectorized path too (the per-instance path pads after the
    transform; the whole-batch mean/scale must not leak -mean*scale
    into them)."""
    knobs = [("round_batch", "0"), ("divideby", "256"),
             ("mean_value", "120,117,104")]

    def chain(vec, n):
        ba = BatchAdapter(AugmentAdapter(ImageSource(n=n)))
        ba.set_param("batch_size", "8")
        ba.set_param("input_shape", "3,16,16")
        ba.set_param("augment_vectorize", str(vec))
        for k, v in knobs:
            ba.set_param(k, v)
        ba.init()
        return list(ba)

    va, rb = chain(1, n), chain(0, n)
    assert len(va) == len(rb)
    assert va[-1].num_batch_padd > 0
    for bv, br in zip(va, rb):
        np.testing.assert_array_equal(bv.data, br.data)
        np.testing.assert_array_equal(bv.label, br.label)
    pad = va[-1].num_batch_padd
    np.testing.assert_array_equal(va[-1].data[8 - pad:], 0.0)


def test_second_epoch_identical_under_deferral():
    """Per-instance RNG keyed on (seed, index) makes epochs
    reproducible in both modes."""
    ba = _aug_chain([("rand_crop", "1"), ("rand_mirror", "1")], 1)
    e1 = [b.data.copy() for b in ba]
    e2 = [b.data.copy() for b in ba]
    for a, b in zip(e1, e2):
        np.testing.assert_array_equal(a, b)


# -- zero-copy ring assembly ---------------------------------------------


@pytest.mark.parametrize("shape,dt", [((3, 5, 7), np.float32),
                                      ((16,), np.uint8)])
def test_aligned_empty_is_page_aligned(shape, dt):
    a = _aligned_empty(shape, dt)
    assert a.shape == shape and a.dtype == dt
    assert a.ctypes.data % 4096 == 0


def test_ring_buffer_reuse_after_release():
    ba = BatchAdapter(CountingIterator(40))
    ba.set_param("batch_size", "4")
    ba.init()
    ba.before_first()
    assert ba.next()
    b1 = ba.value()
    v1 = b1.data.copy()
    assert b1.release is not None
    b1.release()                      # consumer done: hand the buffer back
    assert ba.next()
    b2 = ba.value()
    # the released buffer was refilled in place
    assert np.shares_memory(b1.data, b2.data)
    np.testing.assert_allclose(b2.data[:, 0], [4, 5, 6, 7])
    np.testing.assert_allclose(v1[:, 0], [0, 1, 2, 3])
    s = ba.ring_snapshot()
    assert s == {"allocated": 1, "reused": 1, "batches": 2}


def test_ring_no_release_no_reuse():
    """A consumer that never releases gets allocate-per-batch — held
    batches are never overwritten."""
    ba = BatchAdapter(CountingIterator(40))
    ba.set_param("batch_size", "4")
    ba.init()
    batches = list(ba)
    assert len(batches) == 10
    for i, b in enumerate(batches):
        np.testing.assert_allclose(b.data[:, 0], np.arange(4) + 4 * i)
    s = ba.ring_snapshot()
    assert s["allocated"] == 10 and s["reused"] == 0


def test_ring_release_idempotent():
    ba = BatchAdapter(CountingIterator(40))
    ba.set_param("batch_size", "4")
    ba.init()
    ba.before_first()
    assert ba.next()
    b = ba.value()
    b.release()
    b.release()                       # double release must not dup the slot
    assert ba.next()
    c1 = ba.value()
    c1_data = c1.data
    assert ba.next()
    c2 = ba.value()
    assert not np.shares_memory(c1_data, c2.data)


def test_test_skipread_head_lease_is_consumed():
    """The cached test_skipread batch is re-served forever: its ring
    lease must be consumed so no release path can recycle it."""
    ba = BatchAdapter(CountingIterator(40))
    ba.set_param("batch_size", "4")
    ba.set_param("test_skipread", "1")
    ba.init()
    ba.before_first()
    assert ba.next()
    assert ba.value().release is None
    first = ba.value().data.copy()
    for _ in range(3):
        assert ba.next()
        np.testing.assert_allclose(ba.value().data, first)


def test_skipread_before_first_resets_when_no_head():
    """Satellite: test_skipread set but the first epoch never produced
    a batch (_head None) — before_first must still reset the epoch
    state so a refilled base serves normally."""
    base = CountingIterator(0)        # empty first epoch
    ba = BatchAdapter(base)
    ba.set_param("batch_size", "4")
    ba.set_param("test_skipread", "1")
    ba.init()
    ba.before_first()
    assert not ba.next()
    base.n = 8                        # data appears
    ba.before_first()
    assert ba.next()                  # reset state serves the new epoch
    np.testing.assert_allclose(ba.value().data[:, 0], [0, 1, 2, 3])
    # and from here the head is cached (skipread semantics)
    assert ba.next()
    np.testing.assert_allclose(ba.value().data[:, 0], [0, 1, 2, 3])


def test_membuffer_consumes_ring_lease():
    """A cached batch is replayed every epoch: membuffer must strip the
    release hook so downstream release cannot recycle its storage."""
    from cxxnet_tpu.io.iter_mem import MemBufferIterator
    ba = BatchAdapter(CountingIterator(12))
    ba.set_param("batch_size", "4")
    mb = MemBufferIterator(ba)
    mb.init()
    e1 = [(b, b.data.copy()) for b in mb]
    assert all(b.release is None for b, _ in e1)
    e2 = [b.data.copy() for b in mb]
    for (_, d1), d2 in zip(e1, e2):
        np.testing.assert_allclose(d1, d2)


# -- prefetch: condvar queue, capacity resize, restart, failure ----------


def test_prefetch_capacity_resize_after_init():
    """Satellite: prefetch_capacity set after init() actually resizes
    the live queue bound."""
    ba = BatchAdapter(CountingIterator(1000))
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=1)
    pf.init()
    pf.set_param("prefetch_capacity", "6")
    assert pf.capacity == 6
    assert pf._q._cap == 6
    pf.before_first()
    # producer can now run ahead by the NEW bound
    deadline = time.time() + 5.0
    while len(pf._q._items) < 6 and time.time() < deadline:
        time.sleep(0.01)
    assert len(pf._q._items) == 6
    got = [b.data[0, 0] for b in [pf.value() for _ in range(3)
                                  if pf.next()]]
    pf.close()


def test_prefetch_restart_race_with_transform():
    """Satellite: before_first bumped mid-device_put (a slow transform
    in flight) must not deliver a stale transformed batch as the first
    batch of the new epoch — the epoch-tag protocol must cover the
    staging pipeline too."""
    base = CountingIterator(1000)
    ba = BatchAdapter(base)
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=2)

    def slow_put(b):
        time.sleep(0.002)             # an in-flight transfer window
        return DataBatch(data=b.data + 0.0, label=b.label,
                         inst_index=b.inst_index,
                         num_batch_padd=b.num_batch_padd)

    pf.set_transform(slow_put)
    pf.init()
    for trial in range(30):
        pf.before_first()
        assert pf.next()
        assert pf.next()
        if trial % 3 == 0:
            time.sleep(0.005)         # producer mid-transform, queue full
        pf.before_first()
        assert pf.next()
        first = pf.value()
        assert first.data[0, 0] == 0, \
            "stale transformed batch after restart: row %r" \
            % first.data[0, 0]
    pf.close()


def test_prefetch_transform_releases_host_buffer():
    """With a transform attached (the device_put stage), the producer
    returns host ring buffers after the copy completes — steady-state
    assembly reuses instead of allocating."""
    ba = BatchAdapter(CountingIterator(10000))
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=2)
    pf.set_transform(lambda b: DataBatch(data=b.data.copy(),
                                         label=b.label.copy(),
                                         inst_index=b.inst_index,
                                         num_batch_padd=b.num_batch_padd))
    pf.init()
    pf.before_first()
    for _ in range(40):
        assert pf.next()
    snap = pipeline_snapshot(pf)
    pf.close()
    assert snap["buffers_reused"] > 0
    assert snap["buffer_reuse_rate"] > 0.5
    assert snap["h2d_batches"] >= 40
    # H2D time is the io.h2d_* spans': a chain with no monitor attached
    # reads no clock
    assert snap["h2d_ms"] == 0.0


def test_prefetch_never_releases_aliasing_transform():
    """A transform whose output ALIASES the host ring buffer (zero-copy
    device_put on host-backed backends) must disable release: recycling
    the buffer would overwrite batches still sitting in the queue.
    Reproduces the CPU jax.device_put zero-copy corruption with plain
    numpy aliasing."""
    ba = BatchAdapter(CountingIterator(200))
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=4)
    # identity-aliasing transform: same arrays, new wrapper (what
    # zero-copy device_put amounts to)
    pf.set_transform(lambda b: DataBatch(data=b.data, label=b.label,
                                         inst_index=b.inst_index,
                                         num_batch_padd=b.num_batch_padd))
    pf.init()
    pf.before_first()
    for n in range(16):
        assert pf.next()
        time.sleep(0.003)             # let the producer run far ahead
        got = pf.value().data[0, 0]
        assert got == n * 5, \
            "batch %d served row %r: ring recycled an aliased buffer" \
            % (n, got)
    assert pf._release_safe is False
    snap = pipeline_snapshot(pf)
    assert snap["buffers_reused"] == 0
    pf.close()


def test_prefetch_producer_failure_propagates():
    """A transform/decode exception in the producer thread must raise
    in the consumer, not hang it on an empty queue forever."""
    ba = BatchAdapter(CountingIterator(100))
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=2)

    def boom(b):
        raise ValueError("decode exploded")

    pf.set_transform(boom)
    pf.init()
    pf.before_first()
    with pytest.raises(RuntimeError, match="producer died"):
        pf.next()
    pf.close()


def test_prefetch_failure_survives_before_first_drain():
    """A producer failure delivered while the consumer was NOT in
    next() must not be lost by before_first's queue drain — the
    carrier is the only evidence the producer thread is dead, and
    dropping it would leave the next get() blocked forever."""
    ba = BatchAdapter(CountingIterator(100))
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=2)

    def boom(b):
        raise ValueError("decode exploded")

    pf.set_transform(boom)
    pf.init()
    pf.before_first()                 # producer dies, failure queued
    deadline = time.time() + 5.0
    while pf._thread.is_alive() and time.time() < deadline:
        time.sleep(0.01)
    with pytest.raises(RuntimeError, match="producer died"):
        pf.before_first()             # drain must surface, not swallow
    pf.close()


def test_prefetch_next_after_failure_raises_not_hangs():
    """Re-entering next() after the failure was already delivered must
    re-raise, not block forever on a queue no producer will fill."""
    ba = BatchAdapter(CountingIterator(100))
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=2)
    pf.set_transform(lambda b: (_ for _ in ()).throw(ValueError("x")))
    pf.init()
    pf.before_first()
    with pytest.raises(RuntimeError, match="producer died"):
        pf.next()
    with pytest.raises(RuntimeError, match="producer died"):
        pf.next()                     # second call: guard, not hang
    pf.close()


def test_wait_stats_attach_through_outer_adapter():
    """A membuffer stacked ABOVE the threadbuffer must not lose the
    io_wait histogram (or fake a perfect overlap ratio): the helper
    walks the chain to the nested PrefetchIterator."""
    from cxxnet_tpu.io.iter_batch import enable_chain_wait_stats
    from cxxnet_tpu.io.iter_mem import MemBufferIterator
    ba = BatchAdapter(CountingIterator(20))
    ba.set_param("batch_size", "5")
    pf = PrefetchIterator(ba, capacity=2)
    mb = MemBufferIterator(pf)
    hist = enable_chain_wait_stats(mb)
    assert hist is not None and pf.wait_hist is hist
    mb.init()
    assert len(list(mb)) == 4
    snap = pipeline_snapshot(mb)
    assert snap["batches"] == 4
    pf.close()
    assert enable_chain_wait_stats(CountingIterator(3)) is None


def test_pipeline_snapshot_none_without_adapters():
    assert pipeline_snapshot(CountingIterator(4)) is None


def test_latency_histogram_percentiles():
    from cxxnet_tpu.monitor import LatencyHistogram
    h = LatencyHistogram()
    for ms in [0.1] * 50 + [3.0] * 45 + [40.0] * 5:
        h.observe(ms / 1e3)
    snap = h.snapshot()
    assert snap["p50_ms"] <= snap["p99_ms"] <= snap["max_ms"]
    assert snap["p50_ms"] <= 4.0          # median in the small buckets
    assert snap["p99_ms"] >= 16.0         # tail reaches the slow bucket
    h.reset()
    assert h.snapshot()["p50_ms"] == 0.0


# -- AOT precompile ------------------------------------------------------


_NET = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 8
layer[1->1] = softmax
netconfig = end
input_shape = 1,1,6
batch_size = 8
eta = 0.1
metric[label] = error
"""


def _trainer():
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config
    t = NetTrainer(parse_config(_NET))
    t.init_model()
    return t


def _batches(k=5):
    rng = np.random.RandomState(0)
    return [DataBatch(data=rng.rand(8, 6).astype(np.float32),
                      label=rng.randint(0, 8, (8, 1)).astype(np.float32))
            for _ in range(k)]


def test_precompile_programs_and_zero_compile_events():
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    t = _trainer()
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    n = t.precompile(window=3)
    assert n > 0 and len(t._aot) == n
    pre = [r for r in sink.records if r["event"] == "precompile"]
    assert len(pre) == 1 and pre[0]["programs"] == n
    assert all(r["kind"] == "precompile" for r in sink.records
               if r["event"] == "compile")
    n_compile_records = len([r for r in sink.records
                             if r["event"] == "compile"])
    bs = _batches()
    t.start_round(0)
    t.update(bs[0])                       # per-batch (tail) path
    t.update_many(bs[:3])                 # window path
    validate_records(sink.records)
    # the run itself saw ZERO compiles: every signature was prebuilt
    assert len([r for r in sink.records if r["event"] == "compile"]) \
        == n_compile_records
    steps = [r for r in sink.records if r["event"] == "step"]
    assert steps and all(not s["compile"] for s in steps)


def test_precompile_numerics_identical():
    """AOT dispatch must be bit-for-bit the same program: training with
    precompile on and off from the same seed gives identical weights."""
    bs = _batches()
    ta = _trainer()
    ta.precompile(window=3)
    tb = _trainer()
    for t in (ta, tb):
        t.update(bs[0])
        t.update_many(bs[1:4])
        t.update(bs[4])
    wa = ta.get_weight("fc1", "wmat")
    wb = tb.get_weight("fc1", "wmat")
    np.testing.assert_array_equal(wa, wb)
    assert ta.last_loss == tb.last_loss


def test_precompile_covers_masked_tail():
    t = _trainer()
    t.precompile(window=2)
    b = _batches(1)[0]
    pad = DataBatch(data=b.data, label=b.label, num_batch_padd=3)
    key = ("update", (8, 6), "float32", (8, 1), False, 0, True)
    assert key in t._aot
    t.update(pad)                          # masked variant runs AOT
    assert float(t.last_loss) > 0


def test_precompile_uncovered_signature_falls_back():
    """A dispatch signature precompile did not cover (here a window of
    2 when only K=3 was prebuilt) goes through jit untouched."""
    t = _trainer()
    t.precompile(window=3)
    keys = set(t._aot)
    t.update_many(_batches(2))
    assert float(t.last_loss) > 0
    assert set(t._aot) == keys             # fallback never grows AOT


def test_precompile_cli_stream_criterion(tmp_path, capsys):
    """The acceptance criterion end-to-end: with ``precompile = 1`` the
    JSONL stream shows zero compile signature events after round 0
    begins (all compiles happen, tagged ``precompile``, before the
    first round_start), and the per-round ``pipeline`` record rides
    beside io_wait."""
    from cxxnet_tpu.main import main
    from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
    from tests.test_main import write_conf
    from tests.test_trainer import synth_idx
    pimg, plab = synth_idx(str(tmp_path), n=300, name="tr")
    pimg2, plab2 = synth_idx(str(tmp_path), n=100, seed=5, name="te")
    conf = write_conf(tmp_path, pimg, plab, pimg2, plab2)
    with open(conf) as f:
        text = f.read()
    text = text.replace("iter = end",
                        "iter = threadbuffer\niter = end", 1)
    with open(conf, "w") as f:
        f.write(text)
    mpath = str(tmp_path / "pre.jsonl")
    assert main([conf, "num_round=2", "monitor=jsonl",
                 "monitor_path=" + mpath, "monitor_flush_period=0",
                 "precompile=1", "save_model=0"]) == 0
    recs = read_jsonl(mpath)
    validate_records(recs)
    first_round = next(i for i, r in enumerate(recs)
                       if r["event"] == "round_start")
    compiles = [(i, r) for i, r in enumerate(recs)
                if r["event"] == "compile"]
    assert compiles, "precompile must record its compiles"
    assert all(i < first_round for i, _ in compiles)
    assert all(r["kind"] == "precompile" for _, r in compiles)
    assert all(not s["compile"] for s in recs if s["event"] == "step")
    pre = [r for r in recs if r["event"] == "precompile"]
    assert len(pre) == 1 and pre[0]["programs"] == len(compiles)
    assert pre[0]["wall_ms"] > 0
    pipes = [r for r in recs if r["event"] == "pipeline"]
    assert [p["round"] for p in pipes] == [0, 1]
    for p in pipes:
        assert 0.0 <= p["buffer_reuse_rate"] <= 1.0
        assert p["h2d_ms"] > 0.0          # the io.h2d_* spans' sum
        assert p["h2d_batches"] == 6      # one per delivered batch
    waits = [r for r in recs if r["event"] == "io_wait"]
    assert all(0 <= w["p50_ms"] <= w["p99_ms"] <= w["max_ms"]
               for w in waits)


def test_compile_cache_dir_writes_entries(tmp_path):
    """compile_cache_dir must actually WRITE cache entries even though
    library-init compiles ran before the dir was configured (jax
    memoizes a 'cache disabled' state that needs resetting)."""
    import os

    import jax
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config
    prev = jax.config.jax_compilation_cache_dir
    try:
        cdir = str(tmp_path / "xla_cache")
        t = NetTrainer(parse_config(_NET)
                       + [("compile_cache_dir", cdir)])
        t.init_model()
        assert jax.config.jax_compilation_cache_dir == cdir
        t.precompile(window=2)
        entries = [f for f in os.listdir(cdir) if f.endswith("-cache")]
        assert entries, "no persistent cache entries written"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()


# -- where the compile cache lives (utils/compile_cache.py) ----------------


@pytest.fixture
def cache_config():
    """Restore jax's cache settings whatever a test sets."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = {n: getattr(jax.config, n) for n in names}
    yield jax.config
    for n, v in prev.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_cache_dir_environment_wins_and_code_sets_none(
        tmp_path, monkeypatch, cache_config):
    from cxxnet_tpu.utils import compile_cache as cc
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv(cc.ENV_VAR, env_dir)
    before = cache_config.jax_compilation_cache_dir
    got = cc.enable_compile_cache(default_dir=str(tmp_path / "default"))
    assert got == env_dir
    # jax reads the variable itself at import; the program sets no
    # directory in code when it is there
    assert cache_config.jax_compilation_cache_dir == before
    assert cache_config.jax_persistent_cache_min_compile_time_secs == 0.0
    # nothing asked for beyond the environment: nothing touched at all
    assert cc.enable_compile_cache() == env_dir


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    import os
    from cxxnet_tpu.utils import compile_cache as cc
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert cc.enable_compile_cache(default_dir=cc.REPO_CACHE_DIR) \
        == cc.REPO_CACHE_DIR
    assert cache_config.jax_compilation_cache_dir == cc.REPO_CACHE_DIR
    # no key, no default: a plain run caches nothing, as before
    assert cc.enable_compile_cache() == ""


def test_cache_dir_key_is_ignored_with_one_warning_under_the_environment(
        tmp_path, monkeypatch, cache_config):
    from cxxnet_tpu.monitor import MemorySink, Monitor, set_global
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils import compile_cache as cc
    from cxxnet_tpu.utils.config import parse_config
    env_dir, key_dir = str(tmp_path / "env"), str(tmp_path / "key")
    monkeypatch.setenv(cc.ENV_VAR, env_dir)
    before = cache_config.jax_compilation_cache_dir
    sink = MemorySink()
    set_global(Monitor(sink))
    try:
        t = NetTrainer(parse_config(_NET)
                       + [("compile_cache_dir", key_dir)])
        t.init_model()                   # calls the helper twice over
        assert cc.enable_compile_cache(key_dir) == env_dir
    finally:
        set_global(None)
    assert cache_config.jax_compilation_cache_dir == before
    warns = [r for r in sink.records if r["event"] == "warning"
             and r["code"] == "compile_cache_dir_ignored"]
    assert len(warns) == 1 and key_dir in warns[0]["message"]
    # a key that AGREES with the environment is no conflict
    sink.clear()
    assert cc.enable_compile_cache(env_dir) == env_dir
    assert sink.records == []


_PINNED_PUT = """
import sys
import jax, numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding
from cxxnet_tpu.utils.compile_cache import (enable_compile_cache,
                                            put_with_layout)
enable_compile_cache(sys.argv[1])
x = np.arange(120, dtype=np.float32).reshape(2, 3, 4, 5)
fmt = Format(Layout(major_to_minor=(3, 2, 1, 0)),
             SingleDeviceSharding(jax.devices()[0]))
a = put_with_layout(x, fmt)
assert np.array_equal(np.asarray(a), x)
print("LAYOUT", tuple(a.format.layout.major_to_minor))
"""


def test_pinned_layout_survives_a_warm_compile_cache(tmp_path):
    """An executable jax 0.9.0 reads back from its persistent cache
    has lost its output layouts, and ``device_put(x, Format)`` is such
    an executable: the first process got the pinned layout, every
    later one the default (seen on the v5e as a warm chip_smoke.py
    refusing its own batch). ``put_with_layout`` salts the relayout
    program so it is never read from the cache: a second and third
    process over the same cache directory still get the pin."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _ in range(3):
        p = subprocess.run(
            [sys.executable, "-c", _PINNED_PUT, str(tmp_path / "cache")],
            cwd=repo, capture_output=True, text=True, timeout=240,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode == 0, p.stderr[-2000:]
        assert "LAYOUT (3, 2, 1, 0)" in p.stdout
    assert os.listdir(tmp_path / "cache")      # the cache was in use
