"""Post-training quantization: calibration round-trip (calibrate ->
save -> verify -> load in serve), per-bucket parity against f32 within
the gate epsilon with zero post-warmup compiles, the serve_dtype knob
on the engine/staging path, and the fp8/bf16 fallbacks. The serve side
reuses the PR 4 smoke harness (ServeSession over a bucket ladder)."""

import os

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.monitor.schema import validate_records
from cxxnet_tpu.nnet.checkpoint import verify_snapshot
from cxxnet_tpu.nnet.quantize import (Calibrator, backend_native,
                                      normalize_serve_dtype,
                                      quantizable, tables_from_blob)
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.serve import ServeSession
from cxxnet_tpu.utils.config import parse_config

# the serve parity gate: quantized top-node outputs (softmax probs)
# must track f32 within this mean absolute error
GATE_EPS = 0.05

CONV_CONF = """
netconfig=start
layer[0->1] = conv:c1
  nchannel = 8
  kernel_size = 3
  pad = 1
  no_bias = 1
layer[1->2] = batch_norm:bn1
layer[2->3] = relu
layer[3->4] = max_pooling
  kernel_size = 2
  stride = 2
layer[4->5] = flatten
layer[5->6] = fullc:fc1
  nhidden = 16
layer[6->7] = relu
layer[7->8] = fullc:fc2
  nhidden = 4
layer[8->8] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 16
eta = 0.05
bn_fold_eval = 1
"""


def _rows(n, seed=0):
    return np.random.RandomState(seed).rand(n, 8, 8, 3) \
        .astype(np.float32)


def _batch(seed=0, n=16):
    rng = np.random.RandomState(seed)
    return DataBatch(data=rng.rand(n, 8, 8, 3).astype(np.float32),
                     label=rng.randint(0, 4, (n, 1)).astype(np.float32))


def _trained_trainer(extra=()):
    """A few updates so BN running stats are non-trivial (zero-init
    stats would make the eval fold degenerate)."""
    t = NetTrainer(parse_config(CONV_CONF) + list(extra))
    t.init_model()
    for i in range(5):
        t.update(_batch(seed=i))
    return t


def _calibrated_tables(trainer, nbatch=4):
    calib = Calibrator(trainer)
    for i in range(nbatch):
        calib.observe(_batch(seed=100 + i))
    return calib.finish()


def test_normalize_serve_dtype():
    assert normalize_serve_dtype("f32") == "float32"
    assert normalize_serve_dtype("bf16") == "bfloat16"
    assert normalize_serve_dtype("int8") == "int8"
    assert normalize_serve_dtype("float8") == "fp8"
    with pytest.raises(ValueError):
        normalize_serve_dtype("int4")


def test_calibrator_collects_per_channel_ranges():
    t = _trained_trainer()
    targets = quantizable(t.net)
    assert {tg.lkey for tg in targets} == {"c1", "fc1", "fc2"}
    tables = _calibrated_tables(t)
    # per-channel activation amax at the layer INPUT, per-out-channel
    # weight amax over the eval-folded weights
    assert tables["c1"]["x_amax"].shape == (3,)
    assert tables["c1"]["w_amax"].shape == (8,)
    assert tables["fc1"]["x_amax"].shape == (128,)
    assert tables["fc2"]["w_amax"].shape == (4,)
    for tab in tables.values():
        assert (tab["x_amax"] >= 0).all() and tab["x_amax"].max() > 0
        assert (tab["w_amax"] > 0).all()


def test_quantize_roundtrip_verify_serve_parity(tmp_path):
    """The acceptance round-trip: calibrate -> save -> ckpt verify ->
    load in serve at serve_dtype=int8 -> per-bucket parity vs the f32
    session within the gate epsilon, zero post-warmup compiles."""
    t = _trained_trainer()
    tables = _calibrated_tables(t)
    t.quant_tables, t.quant_meta = tables, {"dtype": "int8",
                                            "bn_fold_eval": True}
    arrays, meta = t.gather_snapshot()
    assert any(k.startswith("quant/") for k in arrays)
    from cxxnet_tpu.nnet.checkpoint import write_snapshot
    path = str(tmp_path / "0005.model.npz")
    write_snapshot(path, arrays, meta)
    # the digest machinery treats the quantized snapshot as a
    # first-class verified artifact (scales are digest-covered)
    rep = verify_snapshot(path)
    assert rep["ok"], rep

    serve_cfg = parse_config(CONV_CONF) + [("serve_buckets", "1,4,8")]
    sink = MemorySink()
    mon = Monitor(sink)
    s32 = ServeSession(serve_cfg, model_path=path)
    s8 = ServeSession(serve_cfg + [("serve_dtype", "int8")],
                      model_path=path, monitor=mon)
    q = s8.engine.trainer
    assert q.quant_report["active"]
    assert q.quant_report["layers"] == 3
    try:
        for n in (1, 2, 3, 4, 5, 8, 16):     # every bucket + fill level
            rows = _rows(n, seed=n)
            want = s32.predict(rows)
            got = s8.predict(rows)
            assert got.shape == want.shape
            raw32 = s32.engine.run(rows)
            raw8 = s8.engine.run(rows)
            assert np.abs(raw8 - raw32).mean() <= GATE_EPS
        c = s8.engine.counters_snapshot()
        assert c["compile_events"] == 0, c
        assert c["aot_hits"] == c["dispatches"] > 0
    finally:
        sum8 = s8.close()
        s32.close()
    assert sum8["compile_events"] == 0
    errs = validate_records(sink.records)
    assert not errs
    kinds = {r["event"] for r in sink.records}
    assert "quantized_model" in kinds      # emitted on monitor attach
    # scales round-trip through the blob loader
    from cxxnet_tpu.nnet.checkpoint import read_snapshot
    blob, meta2 = read_snapshot(path)
    t2 = tables_from_blob(blob)
    assert set(t2) == set(tables)
    np.testing.assert_array_equal(t2["c1"]["w_amax"],
                                  tables["c1"]["w_amax"])
    assert meta2["quantized"]["dtype"] == "int8"


def test_serve_dtype_int8_without_tables_raises(tmp_path):
    t = _trained_trainer()
    path = str(tmp_path / "0005.model.npz")
    t.save_model(path)
    q = NetTrainer(parse_config(CONV_CONF) + [("serve_dtype", "int8")])
    with pytest.raises(ValueError, match="calibrated snapshot"):
        q.load_model(path)


def test_serve_dtype_bf16_needs_no_tables(tmp_path):
    t = _trained_trainer()
    path = str(tmp_path / "0005.model.npz")
    t.save_model(path)
    q = NetTrainer(parse_config(CONV_CONF)
                   + [("serve_dtype", "bfloat16")])
    q.load_model(path)
    assert q.quant_report["active"]
    assert q.quant_report["layers"] == 3
    b = _batch(seed=42)
    (ref,) = t._call_pred(t._put_batch_array(b.data), None, (),
                          (t.graph.num_nodes - 1,))
    (got,) = q._call_pred(q._put_batch_array(b.data), None, (),
                          (q.graph.num_nodes - 1,))
    # bf16 eval tracks f32 loosely (3-bit mantissa loss per op)
    assert np.abs(np.asarray(got) - np.asarray(ref)).mean() < 0.05


def test_fp8_falls_back_cleanly(tmp_path):
    """serve_dtype=fp8: quantized through e4m3 scales; where the
    backend rejects native fp8 contractions the values still round
    through the fp8 grid and contract in f32 — the load succeeds and
    parity holds (the 'falls back cleanly' contract)."""
    t = _trained_trainer()
    tables = _calibrated_tables(t)
    t.quant_tables, t.quant_meta = tables, {"dtype": "fp8",
                                            "bn_fold_eval": True}
    arrays, meta = t.gather_snapshot()
    from cxxnet_tpu.nnet.checkpoint import write_snapshot
    path = str(tmp_path / "0005.model.npz")
    write_snapshot(path, arrays, meta)
    q = NetTrainer(parse_config(CONV_CONF) + [("serve_dtype", "fp8")])
    q.load_model(path)
    assert q.quant_report["active"]
    assert q.quant_report["dtype"] == "fp8"
    b = _batch(seed=9)
    (ref,) = t._call_pred(t._put_batch_array(b.data), None, (),
                          (t.graph.num_nodes - 1,))
    (got,) = q._call_pred(q._put_batch_array(b.data), None, (),
                          (q.graph.num_nodes - 1,))
    assert np.abs(np.asarray(got) - np.asarray(ref)).mean() <= GATE_EPS


def test_engine_stages_in_warmed_input_dtype():
    """The staging-dtype pin: a bf16-warmed ladder must stage bf16 (no
    silent up-cast -> recompile hazard on the H2D path), and the
    default f32 engine still casts any caller dtype to f32."""
    import jax.numpy as jnp
    from cxxnet_tpu.parallel import make_mesh
    from cxxnet_tpu.serve import InferenceEngine
    from tests.test_trainer import MLP_CONF, make_trainer

    t = make_trainer(MLP_CONF, extra=[("serve_dtype", "bfloat16")],
                     mesh=make_mesh(1, 1))
    eng = InferenceEngine(t, buckets=(1, 4, 8),
                          input_dtype=jnp.bfloat16)
    eng.warmup()
    bf16 = np.dtype(jnp.bfloat16)
    for src in (np.float32, np.float64, np.uint8):
        staged = eng.stage(np.zeros((3, 256), src))
        assert staged.data.dtype == bf16
        eng.dispatch(staged)
    c = eng.counters_snapshot()
    assert c["compile_events"] == 0, c
    assert c["aot_hits"] == c["dispatches"] > 0

    t32 = make_trainer(MLP_CONF, mesh=make_mesh(1, 1))
    e32 = InferenceEngine(t32, buckets=(1, 4))
    e32.warmup()
    staged = e32.stage(np.zeros((2, 256), np.float64))
    assert staged.data.dtype == np.float32
    e32.dispatch(staged)
    assert e32.counters_snapshot()["compile_events"] == 0


def test_quantize_task_cli(tmp_path):
    """task=quantize end to end through the CLI driver: calibrate over
    the (neutralized) train-iterator fallback, gate parity, and write
    the verified quantized snapshot beside the source."""
    from cxxnet_tpu.main import main
    from tests.test_trainer import synth_idx

    src = str(tmp_path / "0005.model.npz")
    pimg, plab = synth_idx(str(tmp_path), n=64, name="cal")
    conf = """
data = train
iter = mnist
  path_img = "%s"
  path_label = "%s"
  silent = 1
iter = end

netconfig=start
layer[+1:h] = fullc:fc1
  nhidden = 32
  init_sigma = 0.05
layer[+1] = relu
layer[h->o] = fullc:fc2
  nhidden = 4
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,256
batch_size = 32
eta = 0.1
""" % (pimg, plab)
    mlp = NetTrainer(parse_config(conf))
    mlp.init_model()
    rng = np.random.RandomState(3)
    for i in range(3):
        mlp.update(DataBatch(
            data=rng.rand(32, 256).astype(np.float32),
            label=rng.randint(0, 4, (32, 1)).astype(np.float32)))
    mlp.save_model(src)
    cp = str(tmp_path / "run.conf")
    with open(cp, "w") as f:
        f.write(conf)
    rc = main([cp, "task=quantize", "model_in=%s" % src,
               "quantize_batches=2", "silent=1"])
    assert rc == 0
    out = src[:-len(".npz")] + ".int8.npz"
    assert os.path.exists(out)
    rep = verify_snapshot(out)
    assert rep["ok"] and rep["digest"] == "match", rep
    q = NetTrainer(parse_config(conf) + [("serve_dtype", "int8")])
    q.load_model(out)
    assert q.quant_report["active"] and q.quant_report["layers"] == 2


@pytest.mark.parametrize("dt", ["int8", "fp8"])
def test_backend_native_probe_is_cached_and_boolean(dt):
    for op in ("dot", "conv"):
        a = backend_native(dt, op)
        assert isinstance(a, bool)
        assert backend_native(dt, op) is a


def test_bf16_serve_epilogue_keeps_bf16_activations():
    """serve_dtype=bfloat16: the folded conv+BN(+relu) eval path must
    emit bf16 (regression: the epilogue's out_dtype keyed off the
    training compute_dtype only, silently upcasting the whole ladder's
    activations back to f32 mid-graph)."""
    import jax.numpy as jnp
    t = NetTrainer(parse_config(CONV_CONF)
                   + [("serve_dtype", "bfloat16")])
    t.init_model()
    for i in range(2):
        t.update(_batch(seed=i))
    data = jnp.asarray(_rows(4, seed=0))
    nodes, _, _ = t.net.forward(t.params, t.net_state, data,
                                is_train=False)
    # node 1 = the folded conv+BN(+relu) output on the eval path
    assert nodes[1].dtype == jnp.bfloat16, nodes[1].dtype


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_int8_conv_dequant_epilogue_matches_float(relu, dtype):
    """The quantized conv's epilogue over a resident serve tree: the
    accumulator (int32 where the backend contracts int8 natively) times
    ``_r_dequant`` plus the shift (+relu), emitted in the compute dtype,
    against the same integer contraction done in float."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.layers import Shape3, create_layer
    from cxxnet_tpu.nnet.quantize import QuantSpec
    rng = np.random.RandomState(5)
    layer = create_layer("conv", [("nchannel", "24"), ("kernel_size", "3"),
                                  ("pad", "1"), ("dtype", dtype)])
    layer.infer_shape([Shape3(8, 6, 10)])
    x = rng.randn(2, 6, 10, 8).astype(np.float32)
    w = rng.randn(3, 3, 8, 24).astype(np.float32)
    xs = float(np.abs(x).max() / 127)
    ws = np.abs(w).max(axis=(0, 1, 2)) / 127
    q = QuantSpec("int8", xs, jnp.asarray(ws),
                  backend_native("int8", "conv"))
    layer._quant = q
    shift = rng.randn(24).astype(np.float32)
    params = {"wmat": q.quantize_w(jnp.asarray(w)),
              "_r_dequant": q.dequant_vec(),
              "_r_shift_relu" if relu else "_r_shift": jnp.asarray(shift)}
    (y,), _ = layer.forward(params, {}, [jnp.asarray(x)], False, None)
    assert y.dtype == jnp.dtype(dtype)
    xq = np.clip(np.round(x / xs), -127, 127)
    wq = np.clip(np.round(w / ws), -127, 127)
    # integer products and sums below 2**24: exact in float32
    acc = jax.lax.conv_general_dilated(
        xq, wq, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    ref = np.asarray(acc, np.float64) * (xs * ws) + shift
    if relu:
        ref = np.maximum(ref, 0)
    tol = 1e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), ref, rtol=tol,
                               atol=tol * np.abs(ref).max())


@pytest.mark.parametrize("relu", [0, 1])
@pytest.mark.parametrize("serve_dtype", ["float32", "bfloat16"])
def test_bn_fold_eval_resident_tree_matches_the_unfolded_net(relu,
                                                            serve_dtype):
    """freeze_serve_weights under bn_fold_eval: the conv's weight arrives
    folded (times the BN's scale, in the serve dtype) with the shift
    beside it, keyed by whether the relu rides along, and the eval output
    matches conv -> batch_norm -> relu on the masters."""
    import jax.numpy as jnp
    t = _trained_trainer([("bn_fuse_relu", str(relu)),
                          ("serve_dtype", serve_dtype)])
    res = t.freeze_serve_weights()
    tree = res.tree["c1"]
    assert ("_r_shift_relu" if relu else "_r_shift") in tree
    assert tree["wmat"].dtype == jnp.dtype(serve_dtype)
    ref = NetTrainer(parse_config(CONV_CONF.replace("bn_fold_eval = 1",
                                                    "bn_fold_eval = 0")))
    ref.init_model()
    for lk, pt in t.params.items():
        for tag in pt:
            ref.set_weight(lk, tag, t.get_weight(lk, tag))
    for lk, st in t.net_state.items():
        ref.net_state[lk] = dict(st)
    data = jnp.asarray(_rows(8, seed=9))
    top = t.graph.num_nodes - 1
    got = t.net.forward(res.tree, t.net_state, data, is_train=False)[0][top]
    want = ref.net.forward(ref.params, ref.net_state, data,
                           is_train=False)[0][top]
    tol = 1e-5 if serve_dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
