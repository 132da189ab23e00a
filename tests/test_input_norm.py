"""Device-side input normalisation: the consumer of an iterator chain
asks it for ``(mean, scale)`` (``IIterator.defer_normalize``), the chain
then delivers uint8 pixels, cropped and mirrored only, and the trainer
runs ``(float32(x) - mean) * scale`` as the first ops of the step. The
values the first layer sees are the host path's to the bit.
"""

import os

import numpy as np
import pytest

from cxxnet_tpu.io import create_iterator
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.io.iter_batch import PrefetchIterator, pipeline_snapshot
from cxxnet_tpu.io.recordio import (RecordIOWriter, pack_image_record,
                                    pack_raw_tensor_record)

SIZE, CROP, BATCH, N = 32, 24, 8, 19      # 19 = two batches + a tail of 3


def _images(n=N, seed=11):
    return np.random.RandomState(seed).randint(
        0, 256, (n, SIZE, SIZE, 3)).astype(np.uint8)


@pytest.fixture(scope="module")
def recs(tmp_path_factory):
    """One JPEG and one raw-tensor archive of the same images, and a
    mean image of the crop's shape."""
    import cv2
    root = tmp_path_factory.mktemp("norm")
    paths = {}
    for fmt in ("jpeg", "raw"):
        p = str(root / (fmt + ".rec"))
        w = RecordIOWriter(p)
        for i, img in enumerate(_images()):
            if fmt == "raw":
                w.write_record(pack_raw_tensor_record(i, float(i % 5), img))
            else:
                ok, buf = cv2.imencode(".jpg", img[:, :, ::-1])
                assert ok
                w.write_record(pack_image_record(i, float(i % 5),
                                                 buf.tobytes()))
        w.close()
        paths[fmt] = p
    mean = (np.random.RandomState(5).rand(CROP, CROP, 3) * 200) \
        .astype(np.float32)
    np.save(str(root / "mean.npy"), mean)
    paths["mean"] = str(root / "mean")     # the adapter appends .npy
    return paths


NORMS = {
    "mean_value": [("mean_value", "123,117,104")],
    "image_mean": [("image_mean", None)],          # path filled in
    "scale": [("mean_value", "123,117,104"), ("scale", "0.017")],
    "divideby": [("divideby", "256")],
}
CROPS = {
    "rand": [("rand_crop", "1"), ("rand_mirror", "1")],
    "fixed": [("crop_y_start", "3"), ("crop_x_start", "5")],
}


def _chain(recs, fmt, knobs, extra=(), threadbuffer=False, batch=BATCH):
    cfg = [("iter", "imgrec")]
    if threadbuffer:
        cfg.append(("iter", "threadbuffer"))
    # after the last iter: a key reaches every adapter of the chain
    cfg += [("path_imgrec", recs[fmt]), ("silent", "1"),
            ("round_batch", "0")]
    cfg += [(k, recs["mean"] if v is None else v) for k, v in knobs]
    cfg += list(extra)
    it = create_iterator(cfg, [("batch_size", str(batch)),
                               ("input_shape", "3,%d,%d" % (CROP, CROP))])
    it.init()
    return it


def _copy(it):
    return [DataBatch(data=np.array(b.data), label=np.array(b.label),
                      num_batch_padd=b.num_batch_padd) for b in it]


_NET = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 4
  stride = 2
  init_sigma = 0.05
layer[1->2] = relu
layer[2->3] = flatten
layer[3->4] = fullc:fc1
  nhidden = 5
  init_sigma = 0.05
layer[4->4] = softmax
netconfig = end
input_shape = 3,%d,%d
batch_size = %d
eta = 0.001
momentum = 0.9
silent = 1
eval_train = 0
""" % (CROP, CROP, BATCH)


def _trainer(extra=()):
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config
    t = NetTrainer(parse_config(_NET) + list(extra))
    t.init_model()
    return t


def _first_layer_input(t, batch):
    """What the first layer sees, through the trainer's own pred
    program: node 0 of the forward, padded rows included."""
    (v,) = t._call_pred(t._put_batch_array(batch.data), t._put_mask(batch),
                        (), (0,))
    return np.asarray(v)


# -- (a) bit parity ---------------------------------------------------------


@pytest.mark.parametrize("fmt", ["jpeg", "raw"])
@pytest.mark.parametrize("crop", sorted(CROPS))
@pytest.mark.parametrize("norm", sorted(NORMS))
def test_deferred_batch_normalised_on_device_equals_host_batch(
        recs, norm, crop, fmt):
    """Every batch of the epoch, the zero-padded tail included: the
    asked chain's uint8 batch through the trainer's normalisation is
    the host path's float32 batch."""
    knobs = NORMS[norm] + CROPS[crop]
    host = _copy(_chain(recs, fmt, knobs))
    asked = _chain(recs, fmt, knobs)
    t = _trainer()
    spec = asked.defer_normalize(t.adopt_input_norm)
    assert spec is not None and t.input_norm is not None
    if norm == "image_mean":
        assert spec[0].shape == (CROP, CROP, 3)
    dev = _copy(asked)
    assert [b.num_batch_padd for b in dev] == [0, 0, BATCH - N % BATCH]
    for h, d in zip(host, dev):
        assert h.data.dtype == np.float32 and d.data.dtype == np.uint8
        assert h.num_batch_padd == d.num_batch_padd
        np.testing.assert_array_equal(h.label, d.label)
        got = _first_layer_input(t, d)
        assert got.dtype == np.float32
        assert np.array_equal(got, h.data)
    tail = _first_layer_input(t, dev[-1])[N % BATCH:]
    assert not tail.any()                  # filler rows: zeros AFTER norm


@pytest.mark.parametrize("norm", sorted(NORMS))
def test_losses_bit_equal_on_both_paths(recs, norm):
    """Three ``update`` steps (the last on the padded tail) and one
    ``update_many`` of 2: the same losses, bit for bit, whether the
    host or the step normalises."""
    knobs = NORMS[norm] + CROPS["rand"]
    host = _copy(_chain(recs, "jpeg", knobs))
    asked = _chain(recs, "jpeg", knobs)
    th, td = _trainer(), _trainer()
    assert asked.defer_normalize(td.adopt_input_norm) is not None
    dev = _copy(asked)
    losses = {}
    for name, t, bs in (("host", th, host), ("dev", td, dev)):
        out = []
        for b in bs:
            t.update(b)
            out.append(float(t.last_loss))
        t.update_many(bs[:2])
        out.append(float(t.last_loss))
        losses[name] = out
    assert all(np.isfinite(losses["host"]))
    assert losses["host"] == losses["dev"]
    np.testing.assert_array_equal(th.get_weight("c1", "wmat"),
                                  td.get_weight("c1", "wmat"))


def test_wrapped_rows_are_masked_to_zero(recs):
    """``round_batch = 1`` wraps the tail around: the wrapped rows are
    real pixels the mask excludes. The step zeroes what the mask
    excludes; every row that counts equals the host's."""
    knobs = NORMS["mean_value"] + CROPS["rand"]
    wrap = [("round_batch", "1")]
    host = _copy(_chain(recs, "raw", knobs, wrap))[-1]
    asked = _chain(recs, "raw", knobs, wrap)
    t = _trainer()
    asked.defer_normalize(t.adopt_input_norm)
    dev = _copy(asked)[-1]
    nreal = BATCH - dev.num_batch_padd
    assert dev.num_batch_padd == host.num_batch_padd == BATCH - N % BATCH
    assert dev.data[nreal:].any()          # wrapped pixels, not filler
    got = _first_layer_input(t, dev)
    assert np.array_equal(got[:nreal], host.data[:nreal])
    assert not got[nreal:].any()


def test_threadbuffer_chain_switches_at_the_next_epoch(recs):
    """The question may come while the producer thread runs: the switch
    takes hold at the chain's next ``before_first``, in the thread that
    runs the chain, so an epoch is float32 or uint8 and never mixed."""
    knobs = NORMS["mean_value"] + CROPS["rand"]
    it = _chain(recs, "jpeg", knobs, threadbuffer=True)
    assert isinstance(it, PrefetchIterator)
    try:
        before = _copy(it)
        assert {b.data.dtype for b in before} == {np.dtype(np.float32)}
        snap = pipeline_snapshot(it)
        assert (snap["input_dtype"], snap["norm_on_device"]) == ("float32", 0)
        mean, scale = it.defer_normalize()
        after = _copy(it)
        assert {b.data.dtype for b in after} == {np.dtype(np.uint8)}
        snap = pipeline_snapshot(it)
        assert (snap["input_dtype"], snap["norm_on_device"]) == ("uint8", 1)
        for f, u in zip(before, after):
            z = (u.data.astype(np.float32) - mean) * scale
            z[BATCH - u.num_batch_padd:] = 0
            assert np.array_equal(f.data, z)
    finally:
        it.close()


@pytest.mark.parametrize("src", ["img", "imgbin"])
def test_other_image_sources_hand_over_the_same_pixels(src, tmp_path):
    """``img`` and ``imgbin`` decode through the same ``rgb_pixels``:
    asked, their uint8 batch normalised as the step does is the float32
    batch they deliver when nobody asks."""
    import cv2
    from cxxnet_tpu.io.binpage import PageWriter
    rows = []
    if src == "imgbin":
        w = PageWriter(str(tmp_path / "a.bin"))
    for i, img in enumerate(_images(n=6)):
        ok, enc = cv2.imencode(".jpg", img)
        assert ok
        if src == "imgbin":
            w.write(enc.tobytes())
        else:
            (tmp_path / ("i%d.jpg" % i)).write_bytes(enc.tobytes())
        rows.append("%d\t%d\ti%d.jpg" % (i, i % 3, i))
    (tmp_path / "a.lst").write_text("\n".join(rows) + "\n")
    if src == "imgbin":
        w.close()
        cfg = [("iter", "imgbin"), ("image_bin", str(tmp_path / "a.bin"))]
    else:
        cfg = [("iter", "img"), ("image_root", str(tmp_path) + "/")]
    cfg += [("image_list", str(tmp_path / "a.lst")), ("silent", "1")] \
        + NORMS["scale"] + CROPS["rand"]

    def chain():
        it = create_iterator(cfg, [
            ("batch_size", "6"), ("input_shape", "3,%d,%d" % (CROP, CROP))])
        it.init()
        return it

    (host,) = _copy(chain())
    asked = chain()
    mean, scale = asked.defer_normalize()
    (dev,) = _copy(asked)
    assert (host.data.dtype, dev.data.dtype) == (np.float32, np.uint8)
    assert np.array_equal((dev.data.astype(np.float32) - mean) * scale,
                          host.data)


# -- (b) fall-back ----------------------------------------------------------


@pytest.mark.parametrize("knob", [
    ("max_random_contrast", "0.2"), ("max_random_illumination", "9"),
    ("max_rotate_angle", "10"), ("max_shear_ratio", "0.1"),
    ("min_crop_size", "20"), ("augment_vectorize", "0")])
def test_chain_that_cannot_defer_keeps_the_host_path(recs, knob):
    knobs = NORMS["scale"] + CROPS["rand"]
    extra = [knob] + ([("max_crop_size", "28")]
                      if knob[0] == "min_crop_size" else [])
    want = _copy(_chain(recs, "jpeg", knobs, extra))
    it = _chain(recs, "jpeg", knobs, extra)
    t = _trainer()
    assert it.defer_normalize(t.adopt_input_norm) is None
    assert t.input_norm is None            # nothing was adopted
    got = _copy(it)
    for w, g in zip(want, got):
        assert g.data.dtype == np.float32
        assert np.array_equal(w.data, g.data)
    assert pipeline_snapshot(it)["norm_on_device"] == 0


def test_float64_mean_image_stays_on_the_host(recs, tmp_path):
    """The host subtracts a float64 mean image in float64 and rounds
    once; the step would subtract its float32 rounding. Not the same
    bits, so such a chain is not taken over."""
    mean = np.random.RandomState(6).rand(CROP, CROP, 3) * 200
    np.save(str(tmp_path / "m64.npy"), mean)
    knobs = [("image_mean", str(tmp_path / "m64"))] + CROPS["rand"]
    it = _chain(recs, "raw", knobs)
    t = _trainer()
    assert it.defer_normalize(t.adopt_input_norm) is None
    assert t.input_norm is None
    assert _copy(it)[0].data.dtype == np.float32


@pytest.mark.parametrize("src", ["imgrec", "mnist", "csv"])
def test_chain_nobody_asked_delivers_what_it_always_did(recs, src,
                                                        tmp_path):
    """No consumer asked: normalised float32 from an image chain; a
    chain without an augmenter forwards the question and answers no."""
    if src == "imgrec":
        it = _chain(recs, "jpeg", NORMS["scale"] + CROPS["rand"])
        b = _copy(it)[0]
        assert b.data.dtype == np.float32
        assert b.data.min() < 0            # mean subtracted, scaled
        assert pipeline_snapshot(it)["norm_on_device"] == 0
        return
    if src == "mnist":
        from tests.test_trainer import synth_idx
        pimg, plab = synth_idx(str(tmp_path), n=20, name="m")
        cfg = [("iter", "mnist"), ("path_img", pimg), ("path_label", plab),
               ("silent", "1")]
    else:
        p = str(tmp_path / "d.csv")
        with open(p, "w") as f:
            for i in range(20):
                f.write("%d,0.5,0.25,0.125\n" % (i % 2))
        cfg = [("iter", "csv"), ("filename", p), ("label_width", "1"),
               ("iter", "threadbuffer")]
    it = create_iterator(cfg, [("batch_size", "5"),
                               ("input_shape", "1,1,3" if src == "csv"
                                else "1,1,256")])
    it.init()
    try:
        assert it.defer_normalize() is None
        assert all(np.asarray(b.data).dtype == np.float32 for b in it)
    finally:
        it.close()


def test_mismatching_eval_iterator_is_not_deferred(recs):
    """One trainer compiles one normalisation: the training chain's
    spec is adopted, an eval chain offering another keeps normalising
    on the host, one offering the same is taken over."""
    from cxxnet_tpu.main import LearnTask
    train = _chain(recs, "jpeg", NORMS["mean_value"] + CROPS["rand"])
    other = _chain(recs, "jpeg", NORMS["divideby"] + CROPS["fixed"])
    same = _chain(recs, "raw", NORMS["mean_value"] + CROPS["fixed"])
    t = _trainer()
    LearnTask._defer_normalize(t, [train, None, other, same])
    np.testing.assert_array_equal(t.input_norm[0], [123, 117, 104])
    assert t.input_norm[1] == 1
    dt = [_copy(it)[0].data.dtype for it in (train, other, same)]
    assert dt == [np.uint8, np.float32, np.uint8]
    # and the float batch of the chain turned down is evaluated as is
    b = _copy(other)[0]
    assert np.array_equal(_first_layer_input(t, b), b.data)


# -- (c) precompile through the CLI ----------------------------------------


def test_precompile_cli_mean_value_conf_compiles_nothing_in_the_loop(
        recs, tmp_path):
    """``precompile = 1`` with a ``mean_value`` conf: the runner asks
    before it precompiles, precompile lowers for uint8, and the stream
    shows no compile after the first ``round_start``; the ``pipeline``
    records and ``run_start`` say the mechanism engaged."""
    from cxxnet_tpu.main import main
    from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
    block = """
iter = imgrec
  path_imgrec = %s
  mean_value = 123,117,104
  %s
  silent = 1
iter = threadbuffer
iter = end
"""
    conf = str(tmp_path / "run.conf")
    with open(conf, "w") as f:
        f.write("data = train" + block % (
            recs["jpeg"], "rand_crop = 1\n  rand_mirror = 1"))
        f.write("eval = test" + block % (recs["raw"], "round_batch = 0"))
        f.write(_NET + "metric = error\nmodel_dir = %s\n"
                % (tmp_path / "models"))
    mpath = str(tmp_path / "pre.jsonl")
    assert main([conf, "num_round=2", "monitor=jsonl",
                 "monitor_path=" + mpath, "monitor_flush_period=0",
                 "precompile=1", "save_model=0", "dispatch_period=2",
                 "eval_train=1"]) == 0
    recs_ = read_jsonl(mpath)
    validate_records(recs_)
    first_round = next(i for i, r in enumerate(recs_)
                       if r["event"] == "round_start")
    compiles = [(i, r) for i, r in enumerate(recs_)
                if r["event"] == "compile"]
    assert compiles and all(i < first_round for i, _ in compiles)
    assert all(r["kind"] == "precompile" for _, r in compiles)
    assert all("uint8/norm:" in r["signature"] for _, r in compiles)
    steps = [s for s in recs_ if s["event"] == "step"]
    assert steps and all(not s["compile"] for s in steps)
    assert {s["dispatch"] for s in steps} == {"update_many", "update"}
    (start,) = [r for r in recs_ if r["event"] == "run_start"]
    assert start["input_norm"] == {"adopted": True,
                                   "mean": [123.0, 117.0, 104.0],
                                   "scale": 1.0}
    pipes = [r for r in recs_ if r["event"] == "pipeline"]
    assert len(pipes) == 2
    assert all(p["input_dtype"] == "uint8" and p["norm_on_device"] == 1
               for p in pipes)
    evals = [r for r in recs_ if r["event"] == "eval"]
    assert {e["name"] for e in evals} == {"train", "test"}


# -- (d) a float batch lowers to the program it always did ----------------


def _lowered(t, dtype, kind):
    import jax
    n = t.batch_size
    data = jax.ShapeDtypeStruct((n, CROP, CROP, 3), dtype,
                                sharding=t._b_shard)
    label = jax.ShapeDtypeStruct((n, 1), np.float32, sharding=t._b_shard)
    hyper = (len(t._hyper_index), 3)
    u32 = jax.ShapeDtypeStruct((), np.uint32)
    if kind == "update":
        return t._train_step.lower(
            t.params, t.opt_state, t.net_state, t.grad_acc, data, label,
            None, (), jax.ShapeDtypeStruct(hyper, np.float32), u32, u32,
            t._base_key, do_update=True).as_text()
    return t._multi_step.lower(
        t.params, t.opt_state, t.net_state, t.grad_acc, data, label,
        None, (), jax.ShapeDtypeStruct((3,) + hyper, np.float32),
        jax.ShapeDtypeStruct((3,), np.uint32),
        jax.ShapeDtypeStruct((3,), np.bool_), u32, t._base_key).as_text()


@pytest.mark.parametrize("kind", ["update", "run_steps"])
def test_float_batch_lowers_the_same_with_and_without_a_spec(kind):
    t = _trainer()
    plain_f32 = _lowered(t, np.float32, kind)
    plain_u8 = _lowered(t, np.uint8, kind)
    t.set_input_norm(np.asarray([123, 117, 104], np.float32), 0.5)
    assert _lowered(t, np.float32, kind) == plain_f32
    normed_u8 = _lowered(t, np.uint8, kind)
    assert normed_u8 != plain_u8
    # the identity spec is today's uint8 program: cast only
    t2 = _trainer()
    t2.set_input_norm(None, 1.0)
    assert _lowered(t2, np.uint8, kind) == plain_u8


def test_normalisation_ops_carry_the_input_norm_scope():
    """Convert, subtract and multiply lie in the step-level scope
    ``input_norm`` (whatever fusion the compiler puts them in), so
    ``device_scope_coverage`` keeps them."""
    import re

    from cxxnet_tpu.monitor.spans import STEP_SCOPES, scope_path
    t = _trainer()
    t.set_input_norm([123, 117, 104], 0.5)
    t.precompile()
    (key,) = [k for k in t._aot if k[0] == "update" and k[4]]
    known = frozenset(t.net.scope_names + STEP_SCOPES)
    paths = {scope_path(m, known) for m in re.findall(
        r'op_name="([^"]*)"', t._aot[key].as_text())}
    normed = {p for p in paths if "input_norm" in p}
    assert normed and all(p in ("input_norm", "jvp(input_norm)")
                          for p in normed)


def test_spec_is_part_of_the_program_key_and_precompile_dtype():
    """A non-floating dispatch under a spec is keyed with the spec's
    digest; float keys are today's. ``precompile`` lowers for float32
    until a spec is adopted, for uint8 after (the identity included)."""
    ta, tb, tc = _trainer(), _trainer(), _trainer()
    ta.set_input_norm([123, 117, 104], 1.0)
    tb.set_input_norm([123, 117, 105], 1.0)
    tc.set_input_norm(None, 1.0)
    assert ta._dtype_tag(np.dtype(np.uint8)).startswith("uint8/norm:")
    assert ta._dtype_tag(np.dtype(np.uint8)) \
        != tb._dtype_tag(np.dtype(np.uint8))
    assert tc._dtype_tag(np.dtype(np.uint8)) == "uint8"
    for t in (ta, tb, tc):
        assert t._dtype_tag(np.dtype(np.float32)) == "float32"
    plain = _trainer()
    plain.precompile()
    assert {k[2] for k in plain._aot} == {"float32"}
    ta.precompile(window=2)
    assert {k[2] for k in ta._aot} == {ta._dtype_tag(np.dtype(np.uint8))}
    tc.precompile()
    assert {k[2] for k in tc._aot} == {"uint8"}
    # the adopted trainer dispatches its AOT programs for uint8 batches
    b = DataBatch(data=np.zeros((BATCH, CROP, CROP, 3), np.uint8),
                  label=np.zeros((BATCH, 1), np.float32))
    keys = set(ta._aot)
    ta.update(b)
    ta.update_many([b, b])
    assert set(ta._aot) == keys and np.isfinite(ta.last_loss)


def test_readopting_another_spec_forgets_the_old_traces():
    t = _trainer()
    b = DataBatch(data=np.full((BATCH, CROP, CROP, 3), 100, np.uint8),
                  label=np.zeros((BATCH, 1), np.float32))
    t.set_input_norm([100, 100, 100], 1.0)
    assert not _first_layer_input(t, b).any()
    t.set_input_norm([90, 100, 110], 2.0)
    got = _first_layer_input(t, b)
    assert np.array_equal(got[0, 0, 0], [20.0, 0.0, -20.0])
    assert t.adopt_input_norm((np.asarray([90, 100, 110], np.float32),
                               np.float32(2.0)))
    assert not t.adopt_input_norm((None, np.float32(2.0)))


def test_removed_keys_are_gone():
    """``decode_uint8`` and ``precompile_dtype`` said what the code now
    observes: no module, document or example mentions them."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = []
    for top in ("cxxnet_tpu", "doc", "example", "chip_smoke.py",
                "benchmarks", "tools", "wrapper"):
        path = os.path.join(root, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if f.endswith((".py", ".md", ".conf", ".json"))]
        for p in files:
            with open(p, errors="replace") as f:
                text = f.read()
            hits += [(p, k) for k in ("decode_uint8", "precompile_dtype")
                     if k in text]
    assert not hits
