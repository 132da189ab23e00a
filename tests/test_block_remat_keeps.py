"""What a ``remat = block`` segment keeps of the fused attention core.

The kernel's forward rule (layers/pallas_kernels.py: ``_attention_fwd``)
names its two outputs, ``o`` and the row log-sum-exp, and a segment's
``jax.checkpoint`` (nnet/net.py: ``_run_segment``) keeps what carries
those names (layers/base.py: ``BLOCK_REMAT_KEEPS``): the backward kernel
reads both, so a segment that kept neither ran the forward kernel twice
a step. The parent of that change is a segment that keeps no name, which
``BLOCK_REMAT_KEEPS = ()`` gives; a name outside a checkpoint is nothing.
Pallas interpreted, tiny widths at lengths that tile.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import pallas_kernels as pk
from cxxnet_tpu.layers.base import (ATTENTION_KEEPS, BLOCK_REMAT_KEEPS,
                                   MOE_KEEPS)
from cxxnet_tpu.models.kimi_vl import decoder_lm
from cxxnet_tpu.models.trinity import afmoe_lm
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.monitor.schema import OPTIONAL, validate_record
from cxxnet_tpu.nnet import net as net_mod
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config

VOCAB = 32
SHARED = dict(
    vocab=VOCAB, hidden=32, num_layers=2, rms_norm_eps=1e-5, dense_width=48,
    expert_width=24, experts_per_tok=2, experts_held=4, expert_first=0,
    batch_size=2, expert_block=8, loss_chunk=64, bias_sigma=0.01,
    init_sigma=0.1, lr=0.01)


def _conf(kind, seq_len, remat):
    """Two residual blocks with an attention layer each (a dense block,
    an expert block whose widths keep the experts' loop): latent
    attention, or grouped queries (4 on 2 heads) with a window of 200
    keys on the first layer and every earlier key on the second. A
    length of 192 positions is one no tile of the kernel divides."""
    q_block = 128 if seq_len % 128 == 0 else 64
    if kind == "mla":
        return decoder_lm(
            first_k_dense=1, nhead=2, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=16,
            rope_theta=800000.0, n_routed_experts=4, n_shared_experts=1,
            routed_scaling_factor=2.0, seq_len=seq_len, q_block=q_block,
            remat=remat, **SHARED)
    return afmoe_lm(
        num_dense=1, nhead=4, nkvhead=2, head_dim=128, sliding_window=200,
        global_every=2, rope_theta=10000.0, num_experts=4,
        num_shared_experts=1, route_scale=2.0, embed_scale=32 ** 0.5,
        seq_len=seq_len, q_block=q_block, remat=remat, **SHARED)


def _trainer(kind, seq_len=256, remat="block"):
    t = NetTrainer(parse_config(_conf(kind, seq_len, remat))
                   + [("silent", "1"), ("seed", "3")])
    t.init_model()
    return t


def _loss_and_grads(t):
    """(value_and_grad of the trainer's loss over its parameters, a
    batch's parameters): the step's differentiated part, remat as the
    trainer's."""
    seq = t.graph.input_shape[2]
    ids = np.random.RandomState(0).randint(0, VOCAB, (2, seq + 1))
    data = jnp.asarray(ids[:, :-1], jnp.int32)
    label = jnp.asarray(ids[:, 1:], jnp.float32)
    t.net.block_remat = t.remat == "block"
    return jax.value_and_grad(
        lambda p: t.net.loss_fn(p, t.net_state, data, label, None),
        has_aux=True)


def _eqns(jaxpr, out=None):
    """Every equation of a jaxpr, those of its sub-jaxprs after their
    owner."""
    out = [] if out is None else out
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _eqns(j, out)
    return out


def _kernel_calls(t):
    """(forward, backward) attention kernel calls in the grad jaxpr: the
    forward kernel writes two arrays (``o``, ``lse``), the backward one
    a gradient a part of q and of k, and v's."""
    eqns = _eqns(jax.make_jaxpr(_loss_and_grads(t))(t.params).jaxpr)
    calls = [len(e.outvars) for e in eqns if e.primitive.name == "pallas_call"]
    return calls.count(2), len(calls) - calls.count(2)


@pytest.fixture
def keeps_nothing(monkeypatch):
    """The parent's segment: a checkpoint that keeps no name."""
    monkeypatch.setattr(net_mod, "BLOCK_REMAT_KEEPS", ())


# -- (a) the forward kernel once a layer a step ---------------------------


@pytest.mark.parametrize("kind", ["mla", "gqa"])
def test_a_block_segment_runs_each_forward_kernel_once(kind):
    t = _trainer(kind)
    assert [layer.fused_core for layer in t.net.layer_objs
            if hasattr(layer, "fused_core")] == [True, True]
    assert _kernel_calls(t) == (2, 2)


@pytest.mark.parametrize("kind", ["mla", "gqa"])
def test_a_segment_that_keeps_no_name_runs_it_twice(kind, keeps_nothing):
    assert _kernel_calls(_trainer(kind)) == (4, 2)


def test_without_remat_nothing_is_run_again():
    assert _kernel_calls(_trainer("gqa", remat="none")) == (2, 2)


# -- (b) the same values -----------------------------------------------------


@pytest.mark.parametrize("kind", ["mla", "gqa"])
def test_kept_outputs_change_no_bit_of_loss_or_gradients(kind, monkeypatch):
    """The backward kernel reads the ``o`` and ``lse`` the forward pass
    wrote, not a second run's: the same bits, so loss and every
    parameter's gradient equal the policy-free segment's exactly, and
    ``remat = none``'s within test_remat_block_and_loss_chunks_change_
    no_value's tolerances (1e-6 of the loss, 1e-5 a gradient)."""
    t = _trainer(kind)
    (loss, _), grads = jax.jit(_loss_and_grads(t))(t.params)
    monkeypatch.setattr(net_mod, "BLOCK_REMAT_KEEPS", ())
    (loss0, _), grads0 = jax.jit(_loss_and_grads(t))(t.params)
    assert float(loss) == float(loss0) and np.isfinite(float(loss))
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    plain = _trainer(kind, remat="none")
    (loss1, _), grads1 = jax.jit(_loss_and_grads(plain))(plain.params)
    assert float(loss) == pytest.approx(float(loss1), rel=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(grads1)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# -- (c) a segment without the kernel keeps what it kept -------------------


def _shape_of_program(t):
    """Every equation of the grad jaxpr as (primitive, what it makes)."""
    return [(e.primitive.name, [str(v.aval) for v in e.outvars])
            for e in _eqns(jax.make_jaxpr(_loss_and_grads(t))(t.params).jaxpr)]


@pytest.mark.parametrize("kind", ["mla", "gqa"])
def test_the_xla_core_names_nothing(kind, monkeypatch):
    """A length no tile divides: the XLA core, whose segments hold no
    name of attention's (the expert layer's routing carries the only
    names, ``MOE_KEEPS``), so the grad jaxpr is that of a segment that
    keeps the routing alone, equation for equation."""
    t = _trainer(kind, seq_len=192)
    assert not any(layer.fused_core for layer in t.net.layer_objs
                   if hasattr(layer, "fused_core"))
    got = _shape_of_program(t)
    assert "pallas_call" not in {name for name, _ in got}
    names = {e.params["name"] for e in _eqns(jax.make_jaxpr(
        _loss_and_grads(t))(t.params).jaxpr) if e.primitive.name == "name"}
    assert names == set(MOE_KEEPS)
    monkeypatch.setattr(net_mod, "BLOCK_REMAT_KEEPS", MOE_KEEPS)
    assert got == _shape_of_program(t)


# -- (d) a name outside a checkpoint is nothing ------------------------------


def test_attention_outside_a_checkpoint_lowers_to_the_unnamed_text(
        monkeypatch):
    """``_attention``'s value and gradients with no checkpoint around
    them (``remat = none``, a layer on its own) lower to the module text
    of a forward rule that names nothing, the parent's, every operation
    and every function's body; the bits are
    pinned by test_causal_attention.py::test_mlas_call_is_unchanged_to_
    the_bit."""
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    shapes = [(2, 256, 128), (2, 256, 64), (2, 256, 128), (1, 256, 64),
              (2, 256, 128)]
    qn, qr, kn, kr, v = (jax.random.normal(k, s) for k, s in zip(keys, shapes))

    def text():
        def loss(qn, qr, kn, kr, v):
            return jnp.sum(pk._attention((qn, qr), (kn, kr), v, 0.07, 128,
                                         128, 0) ** 2)
        # a private function's name ends in a count of the functions
        # lowered before it, the one thing a name primitive adds to
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))) \
            .lower(qn, qr, kn, kr, v).as_text()
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    named = text()
    names = [e.params["name"] for e in _eqns(jax.make_jaxpr(jax.grad(
        lambda v: jnp.sum(pk._attention((qn, qr), (kn, kr), v, 0.07, 128,
                                        128, 0))))(v).jaxpr)
        if e.primitive.name == "name"]
    assert sorted(names) == sorted(ATTENTION_KEEPS)
    assert set(ATTENTION_KEEPS) < set(BLOCK_REMAT_KEEPS)
    monkeypatch.setattr(pk, "checkpoint_name", lambda x, name: x)
    assert named == text()
    assert not any(n in named for n in BLOCK_REMAT_KEEPS)


# -- (e) the layout record ---------------------------------------------------


def _layout(t):
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    (rec,) = [r for r in sink.records if r["event"] == "layout"]
    return rec


@pytest.mark.parametrize("seq_len,remat,fused,saved", [
    (256, "block", 2, 2),     # fused core, segments: both layers' kept
    (256, "none", 2, 0),      # no segment to keep them
    (192, "block", 0, 0),     # the XLA core names nothing
])
def test_the_layout_record_counts_the_layers_whose_outputs_are_kept(
        seq_len, remat, fused, saved):
    rec = _layout(_trainer("gqa", seq_len=seq_len, remat=remat))
    assert not validate_record(rec)
    assert "attention_saved_layers" in OPTIONAL["layout"]
    assert (rec["attention_layers"], rec["attention_fused_layers"],
            rec["attention_saved_layers"]) == (2, fused, saved)


# -- (f) a convnet's step holds none of it ------------------------------------


def test_a_convnet_step_holds_no_name():
    conf = """
netconfig = start
layer[0->1] = conv:c1
  nchannel = 8
  kernel_size = 3
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 2
  stride = 2
layer[3->4] = flatten
layer[4->5] = fullc:fc
  nhidden = 4
layer[5->5] = softmax
netconfig = end
input_shape = 3,8,8
batch_size = 4
eta = 0.1
"""
    t = NetTrainer(parse_config(conf) + [("silent", "1"), ("seed", "1")])
    t.init_model()
    rec = _layout(t)
    assert (rec["attention_layers"], rec["attention_saved_layers"]) == (0, 0)
    sds = jax.ShapeDtypeStruct
    u32 = sds((), np.uint32)
    args = (t.params, t.opt_state, t.net_state, t.grad_acc,
            sds((4, 8, 8, 3), np.float32, sharding=t._b_shard),
            sds((4, 1), np.float32, sharding=t._b_shard), None, (),
            sds((len(t._hyper_index), 3), np.float32), u32, u32, t._base_key)
    text = t._train_step.lower(*args, do_update=True).as_text(debug_info=True)
    assert "conv.c1" in text
    assert not any(n in text for n in BLOCK_REMAT_KEEPS + ("checkpoint",))
