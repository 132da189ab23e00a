"""The sequence node and the decoder block over it (layers/sequence.py),
against the plain reference (cxxnet_tpu/reference/kimi_vl_a3b.py): every
new layer's forward and gradient, the whole tiny model's loss, gradients
and two Adam steps, the shares of an expert layer adding up to the uncut
layer, picks by ``s + b`` against weights by ``s``, ``task = train``
through the CLI on a token file, and the float programs of the
benchmark's convnets lowering to the text they had before.
"""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import SeqShape, Shape3, create_layer, seq_shape
from cxxnet_tpu.models import kimi_vl_a3b, kimi_vl_a3b_tiny
from cxxnet_tpu.nnet.net import FuncNet
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.reference import kimi_vl_a3b as ref
from cxxnet_tpu.utils.config import parse_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference's names for the sizes kimi_vl_a3b_tiny builds
TINY = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=2, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16, rope_theta=800000.0,
    rms_norm_eps=1e-5, intermediate_size=48, moe_intermediate_size=24,
    n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=2,
    routed_scaling_factor=2.446, norm_topk_prob=True)
T, D = 16, 32


def _layer(kind, cfg, in_shape, seed=0):
    layer = create_layer(kind, [(k, str(v)) for k, v in cfg.items()])
    layer.infer_shape([in_shape] if isinstance(in_shape, tuple)
                      and hasattr(in_shape, "x") else list(in_shape))
    return layer, layer.init_params(jax.random.PRNGKey(seed)), \
        layer.init_state()


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


def _x(seed=1, batch=2, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (batch, T, D))


ATTN = dict(nhead=2, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            kv_lora_rank=16, rope_theta=800000.0, eps=1e-5, q_block=4,
            init_sigma=0.3)
MOE = dict(nexpert=8, topk=3, nhidden=24, nshared=2,
           routed_scaling_factor=2.446, expert_block=4, bias_seed=5,
           bias_sigma=0.5, init_sigma=0.3)


def _pair(kind):
    """(program f(params, x), reference f(params, x), params, x) of one
    layer type, both mapping to an array whose sum is differentiated."""
    x = _x()
    with jax.default_matmul_precision("highest"):
        if kind == "rmsnorm":
            layer, p, st = _layer(kind, {"eps": 1e-5}, seq_shape(T, D))
            p = {"wmat": p["wmat"] + 0.1 * _x(3)[0, 0]}
            return (lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
                    lambda p, x: ref.rms_norm(x, p["wmat"], 1e-5), p, x)
        if kind == "swiglu":
            layer, p, st = _layer(kind, {"nhidden": 48, "init_sigma": 0.3},
                                  seq_shape(T, D))
            return (lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
                    lambda p, x: ref.swiglu(x, p["wgate"], p["wup"],
                                            p["wdown"], None), p, x)
        if kind == "mla_attention":
            layer, p, st = _layer(kind, ATTN, seq_shape(T, D))
            return (lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
                    lambda p, x: jnp.stack([
                        ref.attention(p, x[b], TINY, None, None, False)
                        for b in range(x.shape[0])]), p, x)
        if kind == "moe":
            layer, p, st = _layer(kind, dict(MOE, expert_first=2,
                                             expert_count=4),
                                  seq_shape(T, D))
            held = {k: (v[2:6] if k in ("egate", "eup", "edown") else v)
                    for k, v in p.items()}
            assert all(p[k].shape[0] == 4 for k in ("egate", "eup", "edown"))
            return (lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
                    lambda p, x: ref.moe(p, st["bias"], x.reshape(-1, D),
                                         TINY, (2, 4), None
                                         ).reshape(x.shape), p, x)
        if kind == "embed":
            layer, p, st = _layer(kind, {"nvocab": 64, "nhidden": D},
                                  Shape3(1, 1, T))
            ids = jax.random.randint(jax.random.PRNGKey(2), (2, T), 0, 64)
            return (lambda p, _: layer.forward(p, st, [ids], True, None)[0][0],
                    lambda p, _: p["wmat"][ids], p, x)
        if kind == "add":
            layer, p, st = _layer(kind, {}, [seq_shape(T, D)] * 3)
            return (lambda p, x: layer.forward(
                        p, st, [x, 2 * x, x * x], True, None)[0][0],
                    lambda p, x: x + 2 * x + x * x, p, x)
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["embed", "rmsnorm", "add", "swiglu",
                                  "mla_attention", "moe"])
def test_layer_forward_and_gradient_match_the_reference(kind):
    f, g, p, x = _pair(kind)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, T, D))
    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x), jax.grad(
            lambda p, x: jnp.sum(w * fn(p, x)), argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = both(f)(p, x), both(g)(p, x)
    _close(yf, yg)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b)


def test_sequence_shapes_and_registry():
    s = seq_shape(8, 4)
    assert isinstance(s, SeqShape) and s.is_seq and not s.is_mat
    assert not Shape3(1, 8, 4).is_seq and s == Shape3(1, 8, 4)
    assert seq_shape(1, 4).is_seq and not seq_shape(1, 4).is_mat
    from cxxnet_tpu.layers import array_shape, as_mat, known_layer_type
    assert array_shape(3, s) == (3, 8, 4)
    assert as_mat(jnp.zeros((3, 8, 4))).shape == (3, 32)
    for kind in ("embed", "rmsnorm", "add", "swiglu", "mla_attention",
                 "moe"):
        assert known_layer_type(kind)
    head = create_layer("fullc", [("nhidden", "7"), ("no_bias", "1")])
    assert head.infer_shape([s]) == [seq_shape(8, 7)] \
        and head.out_shapes[0].is_seq
    with pytest.raises(ValueError, match="sequence node"):
        create_layer("swiglu", [("nhidden", "4")]).infer_shape(
            [Shape3(1, 1, 4)])
    with pytest.raises(ValueError, match="matrix of ids"):
        create_layer("embed", [("nvocab", "4"), ("nhidden", "4")]
                     ).infer_shape([Shape3(3, 8, 8)])


# -- the whole tiny model -----------------------------------------------------


def _trainer(dtype="float32", held=(2, 4), extra=()):
    t = NetTrainer(parse_config(kimi_vl_a3b_tiny(
        experts_held=held[1], expert_first=held[0]))
        + [("dtype", dtype), ("seed", "3"), ("silent", "1")] + list(extra))
    t.init_model()
    return t


def _batch(seed=0, batch=2, vocab=64):
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, T + 1))
    return ids[:, :T].astype(np.int32), ids[:, 1:].astype(np.float32)


def _host(t):
    return (jax.tree_util.tree_map(np.asarray, t.params),
            {k: np.asarray(v["bias"]) for k, v in t.net_state.items()})


def test_tiny_model_loss_and_gradients_match_the_reference():
    t = _trainer()
    data, lab = _batch()
    params, biases = _host(t)
    l_ref, g_ref = jax.jit(lambda p: ref.loss_and_grad(
        p, biases, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY,
        held=(2, 4)))(params)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.jit(jax.value_and_grad(
            lambda p: t.net.loss_fn(p, t.net_state, jnp.asarray(data),
                                    jnp.asarray(lab), None),
            has_aux=True))(t.params)
    assert abs(float(loss) - float(l_ref)) < 1e-5
    assert set(g) == set(g_ref)
    for lk in g:
        for tag in g[lk]:
            _close(g[lk][tag], g_ref[lk][tag])


@pytest.mark.parametrize("dtype,tol_loss,tol_step", [
    ("float32", 1e-5, 1e-3), ("bfloat16", 0.03, 0.5)])
def test_two_adam_steps_through_run_steps_match_the_reference(
        dtype, tol_loss, tol_step):
    """``run_steps(batch, 2)`` returns the second step's loss, which
    depends on every gradient and on the update; the parameters after it
    lie within ``tol_step`` of the reference's, as a share of how far the
    reference moved (Adam's first steps move each element by about lr
    whatever its gradient's size, so bfloat16 sign flips of near-zero
    gradients show here long before they show in the loss)."""
    t = _trainer(dtype)
    data, lab = _batch()
    params, biases = _host(t)
    t.run_steps(DataBatch(data=data, label=lab), 2)
    after, losses = jax.jit(lambda p: ref.train_steps(
        p, biases, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY,
        2, lr=0.01, held=(2, 4)))(params)
    assert abs(t.last_loss - float(losses[1])) < tol_loss * float(losses[1])
    assert float(losses[1]) < float(losses[0])
    num = sum(float(jnp.sum((t.params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    den = sum(float(jnp.sum((params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    assert (num / den) ** 0.5 < tol_step
    assert t.update_counter == 2


def test_remat_block_and_loss_chunks_change_no_value():
    data, lab = _batch()
    runs = []
    for extra in ([("remat", "none")], [("remat", "block")]):
        t = _trainer(extra=extra)
        assert t.remat == extra[0][1]
        t.run_steps(DataBatch(data=data, label=lab), 2)
        runs.append((t.last_loss, t.params))
    assert runs[0][0] == pytest.approx(runs[1][0], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(runs[0][1]),
                    jax.tree_util.tree_leaves(runs[1][1])):
        _close(a, b, 1e-5)
    whole = create_layer("softmax", [("batch_size", "2")])
    chunked = create_layer("softmax", [("batch_size", "2"),
                                       ("loss_chunk", "4")])
    logit = _x(4)
    label = jnp.asarray(_batch(vocab=D)[1])
    assert float(whole.loss_value(logit, label, None)) == pytest.approx(
        float(chunked.loss_value(logit, label, None)), rel=1e-6)


# -- routing once a step ----------------------------------------------------


def _routing_ops(t):
    """(``top_k`` calls, router products) in the jaxpr of the gradient of
    the trainer's loss: the router's is the one product at HIGHEST
    precision (forward, a recomputed forward, two transposes)."""
    from test_block_remat_keeps import _eqns
    data, lab = _batch()
    eqns = _eqns(jax.make_jaxpr(jax.grad(
        lambda p: t.net.loss_fn(p, t.net_state, jnp.asarray(data),
                                jnp.asarray(lab), None)[0]))(t.params).jaxpr)
    return (sum(e.primitive.name == "top_k" for e in eqns),
            sum(e.primitive.name == "dot_general"
                and "HIGHEST" in str(e.params["precision"]) for e in eqns))


def test_remat_block_routes_each_expert_layer_once(monkeypatch):
    """A ``remat = block`` segment keeps the expert layer's logits, picks
    and integer plan (layers/base.py: ``MOE_KEEPS``), so the step's
    gradient holds one ``top_k`` a layer, where a segment that keeps none
    of them makes it twice, and one router product fewer a layer."""
    from cxxnet_tpu.layers.base import BLOCK_REMAT_KEEPS, MOE_KEEPS
    from cxxnet_tpu.nnet import net as net_mod
    t = _trainer()
    assert t.remat == "block" and t.net.block_remat
    moe = sum(hasattr(layer, "grouped") for layer in t.net.layer_objs)
    assert moe == 2
    tops, products = _routing_ops(t)
    monkeypatch.setattr(net_mod, "BLOCK_REMAT_KEEPS", tuple(
        n for n in BLOCK_REMAT_KEEPS if n not in MOE_KEEPS))
    assert (tops, products) == (moe, _routing_ops(t)[1] - moe)
    assert _routing_ops(t)[0] == 2 * moe


@pytest.mark.parametrize("remat,saved", [("block", 2), ("none", 0)])
def test_the_layout_record_counts_the_layers_whose_routing_is_kept(
        remat, saved):
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import OPTIONAL, validate_record
    t = _trainer(extra=[("remat", remat)])
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    (rec,) = [r for r in sink.records if r["event"] == "layout"]
    assert not validate_record(rec)
    assert "moe_plan_saved_layers" in OPTIONAL["layout"]
    assert (rec["moe_layers"], rec["moe_plan_saved_layers"]) == (2, saved)


# -- a chip's share of an expert layer ----------------------------------------


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """Sixteen experts over eight shares of two: the parts the shares
    give, with the shared experts (which every chip computes alike)
    counted once, add up to what the uncut reference gives."""
    cfg = dict(TINY, n_routed_experts=16, num_experts_per_tok=4)
    x = _x(7)
    full, p, st = _layer("moe", dict(MOE, nexpert=16, topk=4),
                         seq_shape(T, D))
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(p, st["bias"], x.reshape(-1, D), cfg, None, None)
        shared = ref.swiglu(x.reshape(-1, D), p["sgate"], p["sup"],
                            p["sdown"], None)
        total = jnp.zeros_like(uncut)
        loads = []
        for share in range(8):
            layer, _, _ = _layer("moe", dict(MOE, nexpert=16, topk=4,
                                             expert_first=2 * share,
                                             expert_count=2),
                                 seq_shape(T, D))
            mine = {k: (v[2 * share:2 * share + 2]
                        if k in ("egate", "eup", "edown") else v)
                    for k, v in p.items()}
            (out,), st2 = layer.forward(mine, st, [x], True, None)
            total = total + out.reshape(-1, D) - shared
            loads.append(int(st2["picks_held"]))
            assert int(st2["dropped"]) == 0
            # the share's own reference gives the same part
            _close(out.reshape(-1, D), ref.moe(
                mine, st["bias"], x.reshape(-1, D), cfg,
                (2 * share, 2), None))
    _close(total + shared, uncut)
    assert sum(loads) == 2 * T * 4          # every pick lands on one share
    assert float(jnp.abs(uncut - shared).max()) > 1e-3


def test_picks_come_from_s_plus_b_and_weights_from_s():
    """A bias that lifts two low-scoring experts into the top-k changes
    WHICH experts are picked; their weights are still their own sigmoid
    scores over the picked scores' sum. Weighting by ``s + b`` instead
    gives another result, and so does picking by ``s``."""
    layer, p, st = _layer("moe", dict(MOE, bias_sigma=0.0), seq_shape(T, D))
    xt = _x(11).reshape(-1, D)
    bias = jnp.zeros((8,)).at[jnp.array([1, 6])].set(5.0)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(xt @ p["router"])
        picks, w = layer.route(xt, p["router"], bias)
        plain_picks, _ = layer.route(xt, p["router"], jnp.zeros((8,)))
    picks, w = np.asarray(picks), np.asarray(w)
    assert (np.sort(picks, axis=1)[:, -1] >= 6).all() \
        and all({1, 6} <= set(row) for row in picks)
    assert any(set(a) != set(b) for a, b in zip(picks, np.asarray(
        plain_picks)))
    sp = np.take_along_axis(np.asarray(s), picks, axis=1)
    _close(w, 2.446 * sp / sp.sum(axis=1, keepdims=True))
    spb = sp + np.asarray(bias)[picks]
    assert np.abs(w - 2.446 * spb / spb.sum(axis=1, keepdims=True)
                  ).max() > 0.05
    # the layer and the reference agree under this bias too
    st = dict(st, bias=bias)
    x = xt.reshape(2, T, D)
    with jax.default_matmul_precision("highest"):
        _close(layer.forward(p, st, [x], True, None)[0][0].reshape(-1, D),
               ref.moe(p, bias, xt, TINY, None, None))


def test_moe_dispatch_survives_every_pick_on_one_expert():
    """No capacity: a router that sends every token's first pick to one
    held expert still loses nothing (``dropped`` 0, result = reference)."""
    layer, p, st = _layer("moe", dict(MOE, expert_first=0, expert_count=2,
                                      bias_sigma=0.0), seq_shape(T, D))
    bias = jnp.zeros((8,)).at[0].set(9.0)
    mine = {k: (v[:2] if k in ("egate", "eup", "edown") else v)
            for k, v in p.items()}
    x = _x(13)
    with jax.default_matmul_precision("highest"):
        (out,), st2 = layer.forward(mine, dict(st, bias=bias), [x], True,
                                    None)
        _close(out.reshape(-1, D),
               ref.moe(mine, bias, x.reshape(-1, D), TINY, (0, 2), None))
    assert int(st2["load"][0]) == 2 * T and int(st2["dropped"]) == 0


# -- the trainer's third input kind -------------------------------------------


def test_int32_ids_are_precompiled_and_dispatched_without_a_compile():
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    t = _trainer("bfloat16")
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    assert t.net.ids_input and t.net.input_norm is None
    t.precompile(n_steps=2, per_batch=False)
    assert {k[2] for k in t._aot} == {"int32"}
    data, lab = _batch()
    b = DataBatch(data=data, label=lab)
    keys = set(t._aot)
    t.run_steps(b, 2)
    t.run_steps(b, 2)
    assert set(t._aot) == keys
    validate_records(sink.records)
    steps = [r for r in sink.records if r["event"] == "step"]
    assert [s["tokens"] for s in steps] == [2 * 2 * T] * 2
    assert [s["examples"] for s in steps] == [4, 4]
    (info,) = [r for r in sink.records if r["event"] == "model_info"]
    assert info["tokens_per_example"] == T
    assert info["train_flops_per_token"] * T == pytest.approx(
        info["train_flops_per_example"])
    moes = [r for r in sink.records if r["event"] == "moe"]
    assert len(moes) == 2 and moes[0]["dropped"] == 0
    assert set(moes[0]["layers"]) == {"l1_moe", "l2_moe"}
    assert 0.2 < moes[0]["held_share"] < 0.8      # 4 of 8 experts held
    (scopes,) = [r for r in sink.records if r["event"] == "program_scopes"]
    paths = set(scopes["scopes"].values())
    for want in ("mla_attention.l0_attn", "moe.l1_moe", "embed.embed",
                 "rmsnorm.final_norm", "swiglu.l0_mlp", "fullc.head"):
        assert any(want in p for p in paths), want
    inner = {p.split("/")[-1].split("(")[-1].rstrip(")") for p in paths
             if "moe.l1_moe" in p}
    assert {"route", "dispatch", "experts", "shared"} <= inner


def test_analytic_flops_of_the_cut_configuration():
    """The count the MFU metric divides by, at the benchmark's sizes
    (benchmarks/configs/kimi_vl_a3b.conf: 1 + 5 layers, 8 of 64 experts,
    20,480 rows), from shapes alone: 2.64 GFLOP a token trained, causal
    attention at half the square, routed experts at 6 x 8 / 64 picks a
    token. The conf is the zoo builder's text."""
    from cxxnet_tpu.graph import NetGraph
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi_vl_a3b.conf")) as f:
        text = f.read()
    assert text == kimi_vl_a3b(num_layers=6, vocab=20480, experts_held=8)
    g = NetGraph()
    g.configure(parse_config(text))
    net = FuncNet(g, 2)
    t, d = 8192, 2048
    attn = 2 * (d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d) \
        + 2 * 16 * (192 + 128) * (t + 1) / 2
    dense = 6 * d * 11264
    moe = 2 * d * 64 + 6 * d * 1408 * 2 + 6 * d * 1408 * 6 * 8 / 64
    want = t * (6 * attn + dense + 5 * moe + 2 * d * 20480)
    assert net.analytic_flops_per_example() == pytest.approx(want, rel=1e-9)
    assert 2.60e9 < 3 * want / t < 2.67e9
    assert net.tokens_per_example == t
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))[0]
    n = sum(int(np.prod(w.shape)) for pt in shapes.values()
            for w in pt.values())
    assert n == 668890112


# -- task = train on a token file ---------------------------------------------


def test_token_iterator_windows_and_labels(tmp_path):
    from cxxnet_tpu.io import create_iterator
    ids = np.arange(100, dtype="<i4") % 50
    path = tmp_path / "ids.tok"
    ids.tofile(path)
    it = create_iterator([("iter", "tokens"), ("path_tokens", str(path)),
                          ("seq_len", "8"), ("nvocab", "50"),
                          ("silent", "1")],
                         [("batch_size", "4")])
    it.init()
    it.before_first()
    seen = 0
    while it.next():
        b = it.value()
        assert b.data.dtype == np.int32 and b.data.shape == (4, 8)
        assert b.label.dtype == np.float32 and b.label.shape == (4, 8)
        assert np.array_equal(b.label[:, :-1], b.data[:, 1:])
        assert np.array_equal(b.label[:, -1], (b.data[:, -1] + 1) % 50)
        seen += 4
    assert seen == 12                     # 12 windows of 8 in 100 ids
    bad = create_iterator([("iter", "tokens"), ("path_tokens", str(path)),
                           ("seq_len", "8"), ("nvocab", "10"),
                           ("silent", "1")],
                          [("batch_size", "4")])
    with pytest.raises(ValueError, match="outside the 10 rows"):
        bad.init()


def test_cli_trains_the_tiny_model_from_a_conf_and_the_loss_falls(tmp_path):
    from cxxnet_tpu.main import main
    from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
    rng = np.random.RandomState(0)
    np.tile(rng.randint(0, 64, 37), 40)[:1200].astype("<i4").tofile(
        tmp_path / "train.tok")
    conf = tmp_path / "tiny.conf"
    conf.write_text("""
data = train
iter = tokens
  path_tokens = %s
  nvocab = 64
  shuffle = 1
iter = end
%s
dtype = bfloat16
num_round = 2
max_round = 2
save_model = 0
silent = 1
model_dir = %s
""" % (tmp_path / "train.tok", kimi_vl_a3b_tiny(batch_size=4),
       tmp_path / "models"))
    stream = tmp_path / "run.jsonl"
    assert main([str(conf), "monitor=jsonl",
                 "monitor_path=%s" % stream]) == 0
    recs = read_jsonl(str(stream))
    validate_records(recs)
    losses = [r["loss"] for r in recs if r["event"] == "step"]
    # 18 batches a round, dispatched in windows of dispatch_period
    assert len(losses) >= 6 and all(np.isfinite(losses))
    assert np.mean(losses[-2:]) < 0.5 * losses[0]
    assert all(r["dropped"] == 0 for r in recs if r["event"] == "moe")


# -- the convnets' programs are the parent's ----------------------------------

# sha256 of ``lower(...).as_text()`` of the float32 step programs of the
# benchmark's two convnets (benchmarks/configs/*.conf, batch 8, bfloat16,
# seed 1, on conftest.py's eight virtual CPU devices) as the commit before
# the sequence node lowered them (PR 27's tree: the same lines ran there
# and here in PR 28, and on one device too, where both trees also agree).
# A change that moves one of these has changed what alexnet.* /
# inception_bn.* run: re-measure, then re-pin.
PARENT_TEXT = {
    ("alexnet", "update"):
        "f319cf7d75b28e35a2f98616980ceed68bf09c414e7841c67567efb7ea1e512d",
    ("alexnet", "run_steps"):
        "5212c5d707b4ee48ba5975044ee7ad96b220c20289225ed832444cd5fb15cc10",
    ("inception_bn", "update"):
        "9c1e97c0ec27ad599abc5819b1c5c0962ee62a6278ac7a398d8e6964db219c02",
    ("inception_bn", "run_steps"):
        "1bcb868202ff43d0f21e5f4862cc53128f5449a78a46f9306b31557fa022e5d4",
}


@pytest.mark.parametrize("net,kind", sorted(PARENT_TEXT))
def test_convnet_step_lowers_to_the_parents_text(net, kind):
    size = {"alexnet": 227, "inception_bn": 224}[net]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           net + ".conf")) as f:
        t = NetTrainer(parse_config(f.read()) + [
            ("batch_size", "8"), ("dtype", "bfloat16"), ("silent", "1"),
            ("seed", "1")])
    t.init_model()
    sds = jax.ShapeDtypeStruct
    data = sds((8, size, size, 3), np.float32, sharding=t._b_shard)
    label = sds((8, 1), np.float32, sharding=t._b_shard)
    hyper = (len(t._hyper_index), 3)
    u32 = sds((), np.uint32)
    if kind == "update":
        text = t._train_step.lower(
            t.params, t.opt_state, t.net_state, t.grad_acc, data, label,
            None, (), sds(hyper, np.float32), u32, u32, t._base_key,
            do_update=True).as_text()
    else:
        text = t._multi_step.lower(
            t.params, t.opt_state, t.net_state, t.grad_acc, data, label,
            None, (), sds((3,) + hyper, np.float32), sds((3,), np.uint32),
            sds((3,), np.bool_), u32, t._base_key).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_TEXT[(net, kind)]
    assert not t.net.ids_input and t.net.tokens_per_example == 1
