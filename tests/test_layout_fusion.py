"""Parity pins for the device-step optimization passes:

- channel_pad (nnet/layout.py): channel-aligned training must be
  BIT-EXACT in f32 against the unpadded program — padded channels are
  provably-zero extensions, not math changes — on a plain chain, and
  agree to 1e-5 through ch_concat, a de-pad barrier and extraction
  (the barrier reorders float32 reductions).
- bn_fuse_relu: relu folded into the BN epilogue is the identical
  function composition (bit-exact).
- bn_fold_eval: BN running-stats scale/shift folded into the conv
  weights for eval/pred — reassociation-level rounding only.
- run_steps with update_period > 1: the scanned dispatch equals the
  per-batch dispatch path across accumulation windows.
- the uint32 epoch: exact past 2^24 where the old f32 hyper slot
  rounded.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config


CHAIN_CONF = """
netconfig=start
layer[+1:c1] = conv:cv1
  nchannel = 6
  kernel_size = 3
layer[+1:b1] = batch_norm:bn1
layer[+1:r1] = relu
layer[+1:c2] = conv:cv2
  nchannel = 5
  kernel_size = 3
layer[+1:b2] = batch_norm:bn2
layer[+1:r2] = relu
layer[+1] = flatten
layer[+1:fc] = fullc:fc1
  nhidden = 4
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 3,10,10
batch_size = 8
eta = 0.05
momentum = 0.9
metric = error
"""

# branchy net: ch_concat over unevenly-padded branches + a max-pool
# branch, then an LRN (a layout BARRIER: channel-window sums would see
# the pad gaps) before the head — exercises scatter/merge/de-pad
CONCAT_CONF = """
netconfig=start
layer[+1:s] = conv:cv0
  nchannel = 6
  kernel_size = 3
layer[s->a_c] = conv:cva
  nchannel = 5
  kernel_size = 1
layer[a_c->a_b] = batch_norm:bna
layer[a_b->a] = relu
layer[s->b_c] = conv:cvb
  nchannel = 3
  kernel_size = 3
  pad = 1
layer[b_c->b_b] = batch_norm:bnb
layer[b_b->b] = relu
layer[s->p] = max_pooling
  kernel_size = 3
  stride = 1
  pad = 1
layer[a,b,p->cat] = ch_concat
layer[+1:l] = lrn
  local_size = 3
layer[+1:c2] = conv:cv2
  nchannel = 4
  kernel_size = 3
layer[+1] = flatten
layer[+1:fc] = fullc:fc1
  nhidden = 4
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 3,10,10
batch_size = 8
eta = 0.05
momentum = 0.9
metric = error
"""


def _data(seed=0, n=8, size=10, nclass=4):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, size, size, 3).astype(np.float32),
            rng.randint(0, nclass, (n, 1)).astype(np.float32))


def _train(conf, extra, size=10, steps=2, seed=0):
    data, label = _data(seed, size=size)
    t = NetTrainer(parse_config(conf) + list(extra))
    t.init_model()
    for _ in range(steps):
        t.update(DataBatch(data=data, label=label))
    return t


def _assert_params(ta, tb, exact=True, rtol=0.0, atol=0.0):
    for lk in ta.params:
        for tag in ta.params[lk]:
            a = np.asarray(ta.params[lk][tag])
            b = np.asarray(tb.params[lk][tag])
            if exact:
                np.testing.assert_array_equal(
                    a, b, err_msg="param %s:%s diverged" % (lk, tag))
            else:
                np.testing.assert_allclose(
                    a, b, rtol=rtol, atol=atol,
                    err_msg="param %s:%s diverged" % (lk, tag))


def test_channel_pad_bitexact_training():
    """channel_pad pads conv outputs with provably-zero channels: the
    padded program's params after several updates are BIT-EXACT equal
    to the unpadded program's (f32)."""
    base = _train(CHAIN_CONF, [])
    padded = _train(CHAIN_CONF, [("channel_pad", "8")])
    assert padded.net.layout_summary["layers_padded"] > 0
    _assert_params(base, padded, exact=True)


def test_channel_pad_concat_barrier_and_extract():
    """Through ch_concat (alignment-aware merged segments), a pooling
    branch, and an LRN barrier (de-pad before channel-window sums) —
    training agrees to 1e-5 and extraction returns LOGICAL channels.

    Not bit-exact as the plain chain is: the de-pad barrier in front
    of the LRN changes the order in which XLA adds up float32
    reductions, so every tensor moves by a few units in the last
    place (``bna:bias`` 8.2e-7 relative, the first key compared; the
    largest absolute difference 2.6e-7, on jax 0.9.0's CPU backend).
    rtol 1e-5 is ten times the former; atol 1e-6 is for ``cva:bias``
    and ``cvb:bias``, conv biases under a batch norm whose gradient
    is zero in exact arithmetic, so their values ARE rounding residue
    (1e-7) and no relative bound holds. A padded channel that leaked
    into the math would show at 1e-2."""
    base = _train(CONCAT_CONF, [], size=10)
    padded = _train(CONCAT_CONF, [("channel_pad", "4")], size=10)
    lay = padded.net.node_layouts[
        padded.net.node_index_by_name("cat")]
    assert len(lay) == 3 and any(p for _, p in lay)
    assert padded.net._depad_layers        # the LRN barrier
    _assert_params(base, padded, exact=False, rtol=1e-5, atol=1e-6)
    data, label = _data(0, size=10)
    b = DataBatch(data=data, label=label)
    fa = base.extract_feature(b, "cat")
    fb = padded.extract_feature(b, "cat")
    assert fa.shape == fb.shape            # logical channels (5+3+6)
    assert fa.shape[-1] == 14
    # the batch-norm branches subtract a running mean from activations
    # thirty times the result (438 against 15 here), so the same few
    # units in the last place read 1.1e-3 absolute at ``cat``: 7e-5 of
    # the largest feature. 1e-3 of it is the bound; the pool and conv
    # branches agree to 1e-5 and the predictions below exactly
    np.testing.assert_allclose(fa, fb, rtol=0,
                               atol=1e-3 * np.abs(fa).max())
    np.testing.assert_array_equal(base.predict(b), padded.predict(b))


def test_bn_fuse_relu_bitexact():
    base = _train(CHAIN_CONF, [])
    fused = _train(CHAIN_CONF, [("bn_fuse_relu", "1")])
    assert len(fused.net._identity_layers) == 2
    _assert_params(base, fused, exact=True)


SHARED_BN_CONF = """
netconfig=start
layer[+1:c1] = conv:cv1
  nchannel = 4
  kernel_size = 3
layer[+1:b1] = batch_norm:bnS
layer[+1:r1] = relu
layer[0->e] = conv:cv2
  nchannel = 4
  kernel_size = 3
layer[e->f] = share[bnS]
layer[f->g] = flatten
layer[r1->h] = flatten
layer[g,h->cat] = concat
layer[+1:fc] = fullc:fc1
  nhidden = 4
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 3,10,10
batch_size = 8
eta = 0.05
momentum = 0.9
metric = error
"""


def test_bn_fuse_relu_skips_shared_primaries():
    """A shared BN reuses the primary layer OBJECT: fusing the relu
    into the primary would drag the relu to the share site, whose
    consumer here is a flatten — the pass must skip shared primaries
    so the fused net stays bit-exact with the plain one."""
    base = _train(SHARED_BN_CONF, [])
    fused = _train(SHARED_BN_CONF, [("bn_fuse_relu", "1")])
    assert not fused.net.layer_objs[1].fuse_relu
    _assert_params(base, fused, exact=True)


def test_bn_fold_eval_parity():
    """Folding BN running stats into the conv weights for eval/pred:
    same math modulo reassociation (the scale multiplies the weight
    before the contraction instead of the output after it)."""
    base = _train(CHAIN_CONF, [])
    fold = _train(CHAIN_CONF, [("bn_fold_eval", "1")])
    assert len(fold.net._fold_pairs) == 2
    _assert_params(base, fold, exact=True)  # training untouched
    data, label = _data(1)
    b = DataBatch(data=data, label=label)
    np.testing.assert_array_equal(base.predict(b), fold.predict(b))
    fa = base.extract_feature(b, "b2")
    fb = fold.extract_feature(b, "b2")
    np.testing.assert_allclose(fa, fb, rtol=1e-4, atol=5e-5)


def test_bn_fold_eval_with_fuse_relu_and_pad():
    """All three knobs compose: folded conv applies the fused relu and
    pads its output channels; eval output still matches the plain
    program within rounding."""
    extra = [("bn_fold_eval", "1"), ("bn_fuse_relu", "1"),
             ("channel_pad", "8")]
    base = _train(CHAIN_CONF, [])
    opt = _train(CHAIN_CONF, extra)
    data, label = _data(1)
    b = DataBatch(data=data, label=label)
    fa = base.extract_feature(b, "r2")
    fb = opt.extract_feature(b, "r2")
    assert fa.shape == fb.shape
    np.testing.assert_allclose(fa, fb, rtol=1e-4, atol=5e-5)


def test_run_steps_update_period_matches_per_batch():
    """run_steps now accepts update_period > 1: n scanned steps on one
    resident batch equal n update() calls — accumulation windows close
    in-scan, counters agree, including an odd tail (window left open
    mid-period)."""
    extra = [("update_period", "2"), ("eval_train", "0")]
    data, label = _data(3)
    ta = NetTrainer(parse_config(CHAIN_CONF) + extra)
    tb = NetTrainer(parse_config(CHAIN_CONF) + extra)
    ta.init_model()
    tb.init_model()
    b = DataBatch(data=data, label=label)
    ba = DataBatch(data=ta._put_batch_array(data),
                   label=ta._put_batch_array(label))
    ta.run_steps(ba, 5)                   # 2.5 accumulation windows
    for _ in range(5):
        tb.update(b)
    assert ta.update_counter == tb.update_counter == 2
    assert ta.sample_counter == tb.sample_counter == 1
    _assert_params(ta, tb, exact=False, rtol=1e-6, atol=1e-7)
    # the open window closes identically on both paths
    ta.run_steps(ba, 1)
    tb.update(b)
    assert ta.update_counter == tb.update_counter == 3
    assert ta.sample_counter == tb.sample_counter == 0
    _assert_params(ta, tb, exact=False, rtol=1e-6, atol=1e-7)


def _layout_records(t):
    from cxxnet_tpu.monitor import MemorySink, Monitor
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    return [r for r in sink.records if r["event"] == "layout"]


def test_input_layout_rowmajor_pins_the_compiled_batch_input():
    """input_layout = rowmajor under the installed jax (Format/Layout):
    the probe takes hold, the layout record reports what took hold,
    the AOT program's batch input carries the row-major layout — read
    back from the executable — and a dispatch through it runs and
    matches the unpinned run."""
    data, label = _data(3)
    extra = [("eval_train", "0")]
    pinned = NetTrainer(parse_config(CHAIN_CONF) + extra
                        + [("input_layout", "rowmajor")])
    plain = NetTrainer(parse_config(CHAIN_CONF) + extra)
    pinned.init_model()
    plain.init_model()
    assert pinned.input_layout_effective == "rowmajor"
    assert plain.input_layout_effective == "none"
    assert _layout_records(pinned)[-1]["input_layout"] == "rowmajor"
    assert _layout_records(plain)[-1]["input_layout"] == "none"
    assert pinned.precompile(n_steps=3, per_batch=False) == 1
    (key,) = pinned.programs.aot
    fmt = pinned.programs.aot[key].input_formats[0][4]
    assert tuple(fmt.layout.major_to_minor) == (0, 1, 2, 3)
    placed = pinned._put_batch_array(data)
    assert tuple(placed.format.layout.major_to_minor) == (0, 1, 2, 3)
    for t in (pinned, plain):
        t.run_steps(DataBatch(data=t._put_batch_array(data),
                              label=t._put_batch_array(label)), 3)
    _assert_params(pinned, plain, exact=True)


def test_input_layout_rowmajor_raises_when_it_cannot_be_honoured(
        monkeypatch):
    """Asked for and impossible is an error on a single-process run —
    never a warning and an unpinned run whose records say rowmajor."""
    def refuse(*a, **k):
        raise NotImplementedError("no layouts on this backend")
    monkeypatch.setattr(NetTrainer, "_rowmajor",
                        staticmethod(refuse))
    t = NetTrainer(parse_config(CHAIN_CONF)
                   + [("input_layout", "rowmajor")])
    with pytest.raises(RuntimeError,
                       match="input_layout = rowmajor was asked for"):
        t.init_model()
    # not asked for: nothing probes, nothing raises
    t = NetTrainer(parse_config(CHAIN_CONF))
    t.init_model()
    assert t.input_layout_effective == "none"


def test_input_layout_record_reports_unpinned_under_multiprocess(
        monkeypatch):
    """Multi-process batches cannot carry the pin: the run goes on
    unpinned, and the record says ``none`` — what took hold — not the
    ``rowmajor`` that was asked for."""
    t = NetTrainer(parse_config(CHAIN_CONF)
                   + [("input_layout", "rowmajor")])
    t.init_model()
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    t._probe_input_layout()
    assert t.input_layout == "rowmajor"
    assert t.input_layout_effective == "none"
    assert t._pin_layout(t._b_shard, 4) is t._b_shard


def test_epoch_rides_exact_uint32():
    """The applied-update counter reaches the device exactly: a float32
    hyper slot rounds 2^24+1 to 2^24 (the old bug); the uint32 scalar
    does not — and the packed hyper array no longer carries an epoch
    column at all."""
    t = NetTrainer(parse_config(CHAIN_CONF))
    t.init_model()
    t.update_counter = 2 ** 24 + 1
    e = t._epoch_u32()
    assert e.dtype == np.uint32
    assert int(e) == 2 ** 24 + 1
    assert int(np.float32(2 ** 24 + 1)) == 2 ** 24   # why f32 failed
    assert t._hyper().shape[1] == 3


def test_adam_bias_correction_integer_epoch(rng):
    """AdamUpdater accepts the uint32 epoch and computes the same
    bias-corrected step as with the float epoch at small t."""
    from cxxnet_tpu.updater import create_updater
    upd = create_updater("adam", "wmat", [("eta", "0.01")])
    w = jnp.asarray(rng.randn(4, 3).astype(np.float32))
    g = jnp.asarray(rng.randn(4, 3).astype(np.float32))
    st = upd.init_state(w)
    h32 = {"learning_rate": jnp.float32(0.01),
           "momentum": jnp.float32(0.9), "wd": jnp.float32(0.0),
           "epoch": jnp.float32(7)}
    hu32 = dict(h32, epoch=jnp.uint32(7))
    w1, _ = upd.apply(w, g, st, h32)
    w2, _ = upd.apply(w, g, st, hu32)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2),
                               rtol=1e-7)
