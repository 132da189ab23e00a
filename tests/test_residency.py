"""Device-resident serve weight tree + zero-copy dispatch (round 11).

Pins the tentpole contracts:

- int8/fp8 serve weights quantize exactly ONCE at load: the traced
  pred graph contains no round/clip/cast over weight-shaped tensors
  (asserted on the jaxpr), and outputs are bit-identical to the
  legacy per-dispatch path;
- every bucket executable of a model shares one device weight tree
  (resident bytes are independent of the ladder size, ~1x model size);
- ``dispatch`` slices valid rows on device BEFORE the D2H
  materialization, so transferred bytes scale with nvalid, not the
  bucket;
- ``serve_device_mem_budget`` rejects an over-budget load with the
  typed :class:`ResidencyBudgetError` (engine freeze AND router
  register/swap), leaving the old model set serving;
- export -> boot of a residency-enabled bundle keeps zero compile
  records and byte-identical outputs.
"""

import numpy as np
import pytest

import jax

from cxxnet_tpu.artifact.registry import ResidencyBudgetError
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.monitor.schema import validate_records
from cxxnet_tpu.nnet.quantize import Calibrator
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import make_mesh
from cxxnet_tpu.serve import (InferenceEngine, ServeSession,
                              run_closed_loop)
from cxxnet_tpu.serve.router import ModelRouter, UnknownModelError
from cxxnet_tpu.utils.config import parse_config

FOLD_CONF = """
netconfig=start
layer[+1:c1] = conv:c1
  kernel_size = 3
  nchannel = 8
  pad = 1
layer[+1:b1] = batch_norm:b1
layer[+1] = relu
layer[+1] = flatten
layer[+1:f1] = fullc:f1
  nhidden = 10
layer[+0] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 8
bn_fold_eval = 1
bn_fuse_relu = 1
eta = 0.1
"""

CONV_W_SHAPE = (3, 3, 3, 8)
FULLC_W_SHAPE = (512, 10)


def _rows(n, seed=0):
    return np.random.RandomState(seed).rand(n, 8, 8, 3) \
        .astype(np.float32)


def _batch(n, seed=0):
    return DataBatch(data=_rows(n, seed),
                     label=np.zeros((n, 1), np.float32))


def _trainer(extra=(), seed_weights=None, monitor=None):
    t = NetTrainer(parse_config(FOLD_CONF) + list(extra),
                   mesh=make_mesh(1, 1))
    t.init_model()
    if monitor is not None:
        t.set_monitor(monitor)
    if seed_weights is not None:
        src = seed_weights
        for lk, pt in src.params.items():
            for tag in pt:
                t.set_weight(lk, tag, src.get_weight(lk, tag))
        for lk, st in src.net_state.items():
            t.net_state[lk] = dict(st)
    return t


@pytest.fixture(scope="module")
def calibrated():
    """One trained+calibrated source model shared by the int8 tests."""
    t0 = NetTrainer(parse_config(FOLD_CONF), mesh=make_mesh(1, 1))
    t0.init_model()
    t0.update(_batch(8))
    cal = Calibrator(t0)
    cal.observe(_batch(8))
    return t0, cal.finish()


def _int8_trainer(calibrated, residency):
    t0, tables = calibrated
    t = _trainer([("serve_weight_residency", str(residency))],
                 seed_weights=t0)
    t.set_quantization(tables, {"dtype": "int8", "bn_fold_eval": True},
                       dtype="int8")
    return t


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                yield from _all_eqns(sub)


def _weight_rounds(trainer):
    """round/clip eqns over weight-shaped tensors in the traced pred
    graph — the per-dispatch quantize pass the freeze removes."""
    params_t, state_t = trainer._pred_operands()
    top = trainer.graph.num_nodes - 1
    jaxpr = jax.make_jaxpr(
        lambda p, s, d: trainer.net.forward(p, s, d,
                                            is_train=False)[0][top]
    )(params_t, state_t, _rows(8))
    wshapes = {CONV_W_SHAPE, FULLC_W_SHAPE}
    return [e for e in _all_eqns(jaxpr.jaxpr)
            if e.primitive.name in ("round", "round_nearest_even")
            and tuple(e.outvars[0].aval.shape) in wshapes]


# -- quantize exactly once at load ---------------------------------------


def test_int8_weights_quantize_once_at_load(calibrated):
    """The resident pred graph carries NO weight-shaped round ops (the
    weights arrive pre-quantized as arguments); the legacy graph
    rounds both weight tensors per dispatch. Outputs bit-identical."""
    legacy = _int8_trainer(calibrated, 0)
    resident = _int8_trainer(calibrated, 1)
    assert len(_weight_rounds(legacy)) == 2     # conv + fullc weights
    assert _weight_rounds(resident) == []
    b = _batch(8, seed=3)
    assert np.array_equal(legacy.predict(b), resident.predict(b))


def test_fold_residency_bit_parity_and_invalidation(calibrated):
    """bn_fold_eval prefold parity (engine path, padded + full
    buckets), and a weight mutation invalidates the frozen tree."""
    t0, _ = calibrated
    outs = {}
    for res in (0, 1):
        t = _trainer([("serve_weight_residency", str(res))],
                     seed_weights=t0)
        eng = InferenceEngine(t, buckets=(4, 8))
        eng.warmup()
        outs[res] = (eng.run(_rows(3, seed=5)),
                     eng.run(_rows(8, seed=6)))
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][1], outs[1][1])
    # invalidation: a train step must re-freeze before the next pred
    t = _trainer(seed_weights=t0)
    p1 = t.predict(_batch(8, seed=7))
    assert t.programs.residency is not None
    t.update(_batch(8, seed=8))
    assert t.programs.residency is None          # stale tree dropped
    p2 = t.predict(_batch(8, seed=7))
    tl = _trainer([("serve_weight_residency", "0")], seed_weights=t0)
    assert np.array_equal(p1, tl.predict(_batch(8, seed=7)))
    tl.update(_batch(8, seed=8))
    assert np.array_equal(p2, tl.predict(_batch(8, seed=7)))


# -- one shared tree per model -------------------------------------------


def test_resident_bytes_independent_of_bucket_ladder(calibrated):
    """N bucket executables share ONE weight tree: resident bytes for
    a 1-bucket and a 4-bucket engine are identical, and the int8 tree
    stays ~1x model size (masters + quarter-size int8 copies), far
    from the N-bucket closure-copy blowup."""
    sizes = {}
    for buckets in ((8,), (1, 2, 4, 8)):
        t = _int8_trainer(calibrated, 1)
        eng = InferenceEngine(t, buckets=buckets)
        eng.warmup(warm_run=False)
        res = t.programs.residency
        assert res is not None and res.active
        sizes[buckets] = res.total_bytes
        assert res.total_bytes <= 1.6 * res.master_bytes
    assert sizes[(8,)] == sizes[(1, 2, 4, 8)]


def test_weight_residency_record_schema(calibrated):
    sink = MemorySink()
    t = _int8_trainer(calibrated, 1)
    t.set_monitor(Monitor(sink))
    t.predict(_batch(8))
    recs = [r for r in sink.records
            if r["event"] == "weight_residency"]
    assert recs and validate_records(sink.records) == []
    r = recs[-1]
    assert r["bytes"] >= r["master_bytes"] > 0
    assert r["layers"] == 2 and r["dtype"] == "int8" and r["active"]


# -- zero-copy dispatch ---------------------------------------------------


class _D2HProbe:
    """Wraps a device array; records the shape that actually
    materializes to host (``np.asarray`` -> ``__array__``)."""

    def __init__(self, arr, log):
        self._arr = arr
        self._log = log

    def __getitem__(self, sl):
        return _D2HProbe(self._arr[sl], self._log)

    def __array__(self, dtype=None, copy=None):
        self._log.append(tuple(self._arr.shape))
        return np.asarray(self._arr)

    @property
    def shape(self):
        return self._arr.shape


def test_dispatch_transfers_nvalid_rows_not_bucket(calibrated):
    """The D2H materialization happens on the device-sliced valid
    rows: transferred bytes scale with nvalid, never with the padded
    bucket."""
    t0, _ = calibrated
    t = _trainer(seed_weights=t0)
    eng = InferenceEngine(t, buckets=(8,))
    eng.warmup()
    log = []
    orig = t._call_pred
    t._call_pred = lambda *a: [_D2HProbe(v, log) for v in orig(*a)]
    out = eng.dispatch(eng.stage(_rows(3, seed=9)))
    t._call_pred = orig
    assert out.shape[0] == 3
    assert log == [(3, 10)], log          # 3 valid rows, not bucket 8
    snap = eng.counters_snapshot()
    assert snap["d2h_bytes"] == out.nbytes


def test_staging_ring_assembles_request_lists(calibrated):
    """The batcher hands per-request row lists straight to stage;
    varied fills through the preallocated ring stay row-exact, and
    the ring accounts every stage as a reuse or an alloc."""
    t0, _ = calibrated
    t = _trainer(seed_weights=t0)
    eng = InferenceEngine(t, buckets=(4, 8))
    eng.warmup()
    parts = [_rows(2, seed=11), _rows(1, seed=12), _rows(3, seed=13)]
    out = eng.dispatch(eng.stage(parts))          # list protocol
    ref = eng.run(np.concatenate(parts, axis=0))
    assert np.array_equal(out, ref)
    for n in (1, 3, 4, 2, 8, 5):                  # ring reuse cycles
        got = eng.dispatch(eng.stage(_rows(n, seed=20 + n)))
        assert got.shape[0] == n
        assert np.array_equal(got, eng.run(_rows(n, seed=20 + n)))
    snap = eng.counters_snapshot()
    assert snap["staging_reuse"] + snap["staging_alloc"] >= 8


# -- memory budget --------------------------------------------------------


def test_engine_budget_rejects_with_typed_error(calibrated):
    t0, _ = calibrated
    t = _trainer([("serve_device_mem_budget", "0.001")],  # 1 KB
                 seed_weights=t0)
    eng = InferenceEngine(t, buckets=(8,))
    with pytest.raises(ResidencyBudgetError):
        eng.warmup()


def test_router_budget_keeps_old_set_serving(calibrated):
    """Multi-model co-location: per-model resident bytes accounted,
    one tree per model, and an over-budget register/swap raises the
    typed error while the old set keeps serving."""
    t0, _ = calibrated

    def session():
        t = _trainer(seed_weights=t0)
        eng = InferenceEngine(t, buckets=(4, 8))
        return ServeSession([("batch_size", "8")], engine=eng)

    s1, s2 = session(), session()
    try:
        bytes1 = s1.engine.trainer.programs.residency.total_bytes
        assert bytes1 > 0
        # budget fits exactly one model
        router = ModelRouter(mem_budget_bytes=int(1.5 * bytes1))
        e1 = router.register("m1", s1, counter=1, path="a")
        assert e1.resident_bytes == bytes1
        with pytest.raises(ResidencyBudgetError):
            router.register("m2", s2, counter=1, path="b")
        assert router.resolve("m1").session is s1   # still serving
        with pytest.raises(UnknownModelError):
            router.resolve("m2")
        # an over-budget swap is refused and the old entry survives
        router.mem_budget_bytes = bytes1 // 2
        with pytest.raises(ResidencyBudgetError):
            router.swap("m1", s2, counter=2, path="b")
        assert router.resolve("m1").session is s1
        # two models under a sufficient budget: one tree per model
        wide = ModelRouter(mem_budget_bytes=4 * bytes1)
        wide.register("m1", s1, counter=1, path="a")
        wide.register("m2", s2, counter=1, path="b")
        desc = {d["model"]: d for d in wide.describe()}
        assert desc["m1"]["device_mem_bytes"] == bytes1
        assert desc["m2"]["device_mem_bytes"] == bytes1
        assert (s1.engine.trainer.programs.residency.tree
                is not s2.engine.trainer.programs.residency.tree)
    finally:
        s1.close(drain=False)
        s2.close(drain=False)


# -- bundle round trip ----------------------------------------------------


def test_bundle_roundtrip_residency_zero_compiles_byte_identical(
        calibrated, tmp_path):
    """export -> boot of a residency-enabled model: the manifest
    records the weight calling convention, boot re-freezes the same
    tree, every sealed executable installs (zero compile records in
    the whole stream), and outputs are byte-identical to the
    pre-export engine."""
    from cxxnet_tpu.artifact.bundle import bundle_manifest, \
        export_bundle
    from cxxnet_tpu.serve.engine import build_engine
    t0, _ = calibrated
    snap = str(tmp_path / "0001.model.npz")
    t = _trainer(seed_weights=t0)
    t.save_model(snap)
    cfg = parse_config(FOLD_CONF)
    eng = build_engine(cfg, snap, buckets=(4, 8))
    eng.warmup(warm_run=False)
    rows = _rows(5, seed=30)
    before = eng.dispatch(eng.stage(rows))
    bundle = str(tmp_path / "0001.model.bundle")
    export_bundle(eng, bundle)
    assert bundle_manifest(bundle)["weight_residency"] == 1
    sink = MemorySink()
    sess = ServeSession(cfg, model_path=bundle, monitor=Monitor(sink))
    try:
        after = sess.predict(rows)
    finally:
        sess.close()
    assert np.array_equal(before, after)
    assert [r for r in sink.records if r["event"] == "compile"] == []
    art = [r for r in sink.records if r["event"] == "artifact_load"]
    assert art and art[-1]["rebuilds"] == 0 and art[-1]["hits"] > 0
    # a legacy-convention boot cannot call the sealed executables:
    # it falls back to re-lower (one warning, parity intact)
    sink2 = MemorySink()
    sess2 = ServeSession(
        cfg + [("serve_weight_residency", "0")], model_path=bundle,
        monitor=Monitor(sink2))
    try:
        legacy = sess2.predict(rows)
    finally:
        sess2.close()
    assert np.array_equal(before, legacy)
    art2 = [r for r in sink2.records if r["event"] == "artifact_load"]
    assert art2 and art2[-1]["hits"] == 0


# -- closed-loop sweeps leave the weight tree alone -----------------------


def test_resident_bytes_equal_across_client_sweeps():
    """A fresh session a sweep point, driven by ``run_closed_loop``:
    the ``weight_residency`` record's resident device bytes are
    positive and the same at one client and at two (the leak guard: a
    busier batcher adds nothing to the weight tree)."""
    mem = []
    for clients in (1, 2):
        sink = MemorySink()
        mon = Monitor(sink)
        eng = InferenceEngine(_trainer(monitor=mon),
                              buckets=(1, 2, 4, 8), monitor=mon)
        sess = ServeSession(parse_config(FOLD_CONF), engine=eng,
                            monitor=mon)
        try:
            agg = run_closed_loop(sess, _rows(16), clients, 4)
        finally:
            summary = sess.close()
        assert agg["ok"] == clients * 4
        assert summary["compile_events"] == 0
        assert validate_records(sink.records) == []
        res = [r for r in sink.records
               if r["event"] == "weight_residency"]
        mem.append(res[-1]["bytes"])
    assert mem[0] == mem[1] > 0
