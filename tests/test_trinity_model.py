"""Trinity-Mini's block (layers/sequence.py: ``gqa_attention``, ``embed``'s
``scale``; models/trinity.py) against its plain reference
(cxxnet_tpu/reference/trinity_mini.py): the attention layer's values and
gradients over windows, head groupings and RoPE on and off, the whole tiny
model's loss, gradients and two Adam steps through ``NetTrainer``, the
eight shares of its expert block adding up to the uncut reference's, the
records that count the new layers, and the FLOPs and parameters of the
benchmark's cut.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import Shape3, create_layer, seq_shape
from cxxnet_tpu.models import trinity_mini, trinity_mini_tiny
from cxxnet_tpu.nnet.net import FuncNet
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.reference import trinity_mini as ref
from cxxnet_tpu.utils.config import parse_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference's names for the sizes trinity_mini_tiny builds
TINY = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=5, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rope_theta=10000.0, rms_norm_eps=1e-5, sliding_window=6,
    global_attn_every_n_layers=4, intermediate_size=48,
    moe_intermediate_size=24, num_experts=8, num_experts_per_tok=3,
    num_shared_experts=1, route_norm=True, route_scale=2.826,
    mup_enabled=True)
T, D = 16, 32


def _layer(kind, cfg, in_shape, seed=0):
    layer = create_layer(kind, [(k, str(v)) for k, v in cfg.items()])
    layer.infer_shape([in_shape])
    return layer, layer.init_params(jax.random.PRNGKey(seed)), \
        layer.init_state()


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


def _x(seed=1, batch=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, T, D))


# -- the attention layer --------------------------------------------------------


@pytest.mark.parametrize("rope", [0, 1])
@pytest.mark.parametrize("nkvhead", [1, 2, 4])
@pytest.mark.parametrize("window", [0, 6])
def test_gqa_attention_matches_the_reference(window, nkvhead, rope):
    """Values and gradients in float32 at 1e-5: every earlier key and a
    window shorter than the sequence; one key/value head, a divisor of
    the query heads, and a head each; RoPE on and off. ``q_block`` 4, so
    that a block's keys start inside the sequence."""
    layer, p, st = _layer("gqa_attention", dict(
        nhead=4, nkvhead=nkvhead, head_dim=8, window=window, rope=rope,
        rope_theta=10000.0, eps=1e-5, q_block=4, init_sigma=0.3),
        seq_shape(T, D))
    # scales off one, so that a norm left out or misplaced shows
    p = dict(p, qnorm=p["qnorm"] + 0.1 * _x(3)[0, 0, :8],
             knorm=p["knorm"] - 0.1 * _x(4)[0, 0, :8])
    cfg = dict(TINY, num_key_value_heads=nkvhead, sliding_window=window)
    x, w = _x(), _x(9)
    # the reference ties RoPE and the window to one layer kind; this
    # test frees them, so it applies each by hand around ref.attention
    sliding = bool(window)

    def plain(p, x):
        one = lambda xb: _reference_attention(p, xb, cfg, sliding, rope)
        return jnp.stack([one(x[b]) for b in range(x.shape[0])])

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x), jax.grad(
            lambda p, x: jnp.sum(w * fn(p, x)), argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = both(
            lambda p, x: layer.forward(p, st, [x], True, None)[0][0])(p, x), \
            both(plain)(p, x)
    _close(yf, yg)
    assert set(gf[0]) == set(gg[0])
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b)


def _reference_attention(p, x, cfg, sliding, rope):
    """``ref.attention`` with RoPE and the window chosen apart: where they
    agree it is the reference's own layer kind; where they differ the
    reference's RoPE is switched by its ``rope_theta`` (an infinite theta
    turns no pair) or its window by ``sliding_window`` = the sequence."""
    if bool(rope) == sliding:
        return ref.attention(p, x, cfg, sliding, None, None, False)
    if rope:            # RoPE without a window: a window of all the keys
        return ref.attention(p, x, dict(cfg, sliding_window=x.shape[0]),
                             True, None, None, False)
    # a window without RoPE: position 0's angles for every position
    old = ref.rope
    ref.rope = lambda a, theta: a
    try:
        return ref.attention(p, x, cfg, True, None, None, False)
    finally:
        ref.rope = old


def test_a_window_changes_the_result_and_rope_halves_pair_features():
    """Guards of the test above: the window of 6 leaves out keys that the
    full layer sees, and RoPE pairs feature i with i + dim/2."""
    from cxxnet_tpu.layers.sequence import apply_rope, rope_tables
    outs = []
    for window in (0, 6):
        layer, p, st = _layer("gqa_attention", dict(
            nhead=4, nkvhead=2, head_dim=8, window=window, rope=1, eps=1e-5,
            init_sigma=0.3), seq_shape(T, D))
        outs.append(layer.forward(p, st, [_x()], True, None)[0][0])
    assert float(jnp.abs(outs[0] - outs[1])[:, :6].max()) < 1e-6
    assert float(jnp.abs(outs[0] - outs[1])[:, 6:].max()) > 1e-3
    x = jax.random.normal(jax.random.PRNGKey(5), (1, T, 2, 8))
    cos, sin = rope_tables(T, 8, 10000.0)
    _close(apply_rope(x, cos, sin, halves=True)[0], ref.rope(x[0], 10000.0))


def test_embed_scale_multiplies_the_rows():
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, T), 0, 64)
    plain, p, st = _layer("embed", {"nvocab": 64, "nhidden": D},
                          Shape3(1, 1, T))
    scaled, _, _ = _layer("embed", {"nvocab": 64, "nhidden": D,
                                    "scale": "%.17g" % (D ** 0.5)},
                          Shape3(1, 1, T))
    a = plain.forward(p, st, [ids], True, None)[0][0]
    b = scaled.forward(p, st, [ids], True, None)[0][0]
    _close(b, a * D ** 0.5, 1e-6)


def test_gqa_attention_refuses_what_it_cannot_group():
    for bad in (dict(nhead=4, nkvhead=3, head_dim=8),
                dict(nhead=4, nkvhead=2, head_dim=7),
                dict(nhead=4, nkvhead=2, head_dim=8, window=-1),
                dict(nhead=4, head_dim=8)):
        with pytest.raises(ValueError, match="gqa_attention"):
            _layer("gqa_attention", bad, seq_shape(T, D))


# -- the whole tiny model -------------------------------------------------------


def _trainer(dtype="float32", held=(2, 4), extra=()):
    t = NetTrainer(parse_config(trinity_mini_tiny(
        experts_held=held[1], expert_first=held[0]))
        + [("dtype", dtype), ("seed", "3"), ("silent", "1")] + list(extra))
    t.init_model()
    return t


def _batch(seed=0, batch=2, vocab=64):
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, T + 1))
    return ids[:, :T].astype(np.int32), ids[:, 1:].astype(np.float32)


def _host(t):
    return (jax.tree_util.tree_map(np.asarray, t.params),
            {k: np.asarray(v["bias"]) for k, v in t.net_state.items()
             if "bias" in v})


def test_tiny_model_alternates_its_layers_by_the_models_rule():
    t = _trainer()
    attn = [l for l in t.net.layer_objs if hasattr(l, "fused_core")]
    assert [(l.window, l.rope) for l in attn] == [
        (6, 1), (6, 1), (6, 1), (0, 0), (6, 1)]
    assert ref.layer_types(TINY) == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert ref.layer_types(dict(TINY, layer_types=["full_attention"] * 9)) \
        == ["full_attention"] * 5


def test_tiny_model_loss_and_gradients_match_the_reference():
    t = _trainer()
    data, lab = _batch()
    params, biases = _host(t)
    l_ref, g_ref = jax.jit(lambda p: ref.loss_and_grad(
        p, biases, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY,
        held=(2, 4), q_block=8, remat=True))(params)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.jit(jax.value_and_grad(
            lambda p: t.net.loss_fn(p, t.net_state, jnp.asarray(data),
                                    jnp.asarray(lab), None),
            has_aux=True))(t.params)
    assert abs(float(loss) - float(l_ref)) < 1e-5
    assert set(g) == set(g_ref)
    for lk in g:
        for tag in g[lk]:
            _close(g[lk][tag], g_ref[lk][tag], 2e-5)


@pytest.mark.parametrize("dtype,tol_loss,tol_step", [
    ("float32", 1e-5, 1e-3), ("bfloat16", 0.03, 0.5)])
def test_two_adam_steps_through_run_steps_match_the_reference(
        dtype, tol_loss, tol_step):
    """As tests/test_sequence_model.py's for Kimi's block: the second
    step's loss and the parameters after it, as a share of how far the
    reference moved."""
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


def test_records_count_both_attention_kinds_and_the_windows():
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    t = _trainer("bfloat16")
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    t.precompile(n_steps=2, per_batch=False)
    data, lab = _batch()
    t.run_steps(DataBatch(data=data, label=lab), 2)
    validate_records(sink.records)
    (layout,) = [r for r in sink.records if r["event"] == "layout"]
    assert (layout["attention_layers"], layout["attention_fused_layers"],
            layout["attention_window_layers"]) == (5, 0, 4)
    assert (layout["moe_layers"], layout["moe_grouped_layers"]) == (4, 0)
    (scopes,) = [r for r in sink.records if r["event"] == "program_scopes"]
    paths = set(scopes["scopes"].values())
    for want in ("gqa_attention.l0_attn", "gqa_attention.l3_attn",
                 "rmsnorm.l0_attn_post", "rmsnorm.l4_ffn_post", "moe.l1_moe",
                 "swiglu.l0_mlp", "embed.embed", "fullc.head"):
        assert any(want in p for p in paths), want
    assert any("gqa_attention.l3_attn" in p and "core" in p for p in paths)
    moes = [r for r in sink.records if r["event"] == "moe"]
    assert moes and moes[0]["dropped"] == 0
    assert set(moes[0]["layers"]) == {"l%d_moe" % i for i in (1, 2, 3, 4)}
    # a net without attention layers counts none of the three
    from cxxnet_tpu.models import mnist_mlp
    plain = NetTrainer(parse_config(mnist_mlp()) + [("silent", "1")])
    plain.init_model()
    sink2 = MemorySink()
    plain.set_monitor(Monitor(sink2))
    (rec,) = [r for r in sink2.records if r["event"] == "layout"]
    assert (rec["attention_layers"], rec["attention_fused_layers"],
            rec["attention_window_layers"]) == (0, 0, 0)


# -- a chip's share of the block ------------------------------------------------


def test_eight_shares_of_the_expert_block_add_up_to_the_uncut_reference():
    """The guide's share test for afmoe's expert half: sixteen experts
    over eight shares of two, through pre-mlp norm -> moe; the parts the
    shares give, with the one shared expert (which every chip computes
    alike) counted once, add up to the uncut reference's layer, and the
    post-mlp norm and the residual of that sum are the uncut block's."""
    cfg = dict(TINY, num_experts=16, num_experts_per_tok=4)
    moe_cfg = dict(nexpert=16, topk=4, nhidden=24, nshared=1,
                   routed_scaling_factor=2.826, expert_block=4, bias_seed=5,
                   bias_sigma=0.5, init_sigma=0.3)
    h = _x(7)
    full, p, st = _layer("moe", moe_cfg, seq_shape(T, D))
    pre = 1.0 + 0.1 * _x(3)[0, 0]
    post = 1.0 - 0.1 * _x(4)[0, 0]
    with jax.default_matmul_precision("highest"):
        z = ref.rms_norm(h, pre, 1e-5).reshape(-1, D)
        uncut = ref.moe(p, st["bias"], z, cfg, None, None)
        shared = ref.swiglu(z, p["sgate"], p["sup"], p["sdown"], None)
        norm, _, nst = _layer("rmsnorm", {"eps": 1e-5}, seq_shape(T, D))
        zl = norm.forward({"wmat": pre}, nst, [h], True, None)[0][0]
        total, loads = jnp.zeros_like(uncut), []
        for share in range(8):
            layer, _, _ = _layer("moe", dict(moe_cfg,
                                             expert_first=2 * share,
                                             expert_count=2),
                                 seq_shape(T, D))
            mine = {k: (v[2 * share:2 * share + 2]
                        if k in ("egate", "eup", "edown") else v)
                    for k, v in p.items()}
            (out,), st2 = layer.forward(mine, st, [zl], True, None)
            total = total + out.reshape(-1, D) - shared
            loads.append(int(st2["picks_held"]))
            assert int(st2["dropped"]) == 0
            _close(out.reshape(-1, D), ref.moe(mine, st["bias"], z, cfg,
                                               (2 * share, 2), None))
        whole = total + shared
        _close(whole, uncut)
        block = norm.forward({"wmat": post}, nst,
                             [whole.reshape(h.shape)], True, None)[0][0]
        _close(h + block, h + ref.rms_norm(uncut, post, 1e-5
                                           ).reshape(h.shape))
    assert sum(loads) == 2 * T * 4          # every pick lands on one share
    assert float(jnp.abs(uncut - shared).max()) > 1e-3


# -- the benchmark's cut ---------------------------------------------------------


def test_analytic_flops_and_parameters_of_the_cut_configuration():
    """The count the MFU metric divides by, at the benchmark's sizes
    (1 + 4 layers, 16 of 128 experts, 25,024 rows), from shapes alone:
    2.21 GFLOP a token trained, a sliding layer's core at its band
    (14,681,088 of the triangle's 33,558,528 pairs a head), routed
    experts at 8 x 16 / 128 picks a token; and ISSUE 32's table of
    parameters."""
    from cxxnet_tpu.graph import NetGraph
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity_mini.conf")) as f:
        text = f.read()
    # the conf is the zoo builder's text
    assert text == trinity_mini(num_layers=5, num_dense=1, vocab=25024,
                                experts_held=16)
    g = NetGraph()
    g.configure(parse_config(text))
    net = FuncNet(g, 2)
    t, d = 8192, 2048
    attn = [l for l in net.layer_objs if hasattr(l, "fused_core")]
    assert [l.pairs_per_sequence() for l in attn] == [
        14681088.0] * 3 + [33558528.0, 14681088.0]
    proj = 2 * (3 * d * 4096 + 2 * d * 512)
    core = 4 * 32 * 128 * (4 * 14681088 + 33558528)
    dense = 6 * d * 6144
    moe = 2 * d * 128 + 6 * d * 1024 + 6 * d * 1024 * 8 * 16 / 128
    want = t * (5 * proj + dense + 4 * moe + 2 * d * 25024) + core
    assert net.analytic_flops_per_example() == pytest.approx(want, rel=1e-9)
    assert 2.19e9 < 3 * want / t < 2.23e9
    assert net.tokens_per_example == t
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))[0]
    count = lambda keys: sum(int(np.prod(w.shape)) for k in keys
                             for w in shapes[k].values())
    assert count(["l0_attn"]) == 27263232
    assert count(["l0_attn", "l0_mlp"] + ["l0_%s" % n for n in (
        "attn_norm", "attn_post", "ffn_norm", "ffn_post")]) == 65020160
    assert count(["l1_attn", "l1_moe"] + ["l1_%s" % n for n in (
        "attn_norm", "attn_post", "ffn_norm", "ffn_post")]) == 134488320
    assert count(["embed", "head", "final_norm"]) == 102500352
    assert count(shapes) == 705473792
    # the kernel takes all five layers at these shapes
    assert all(l.fused_core for l in attn)


# -- task = train on a token file ------------------------------------------------


def test_cli_trains_the_example_conf_and_the_loss_falls(tmp_path):
    """``example/LM/trinity_mini_tiny.conf`` as it stands (its netconfig is
    the zoo builder's tiny twin), pointed at a token file of the test's."""
    from cxxnet_tpu.main import main
    from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
    rng = np.random.RandomState(0)
    np.tile(rng.randint(0, 64, 37), 40)[:1200].astype("<i4").tofile(
        tmp_path / "train.tok")
    with open(os.path.join(ROOT, "example", "LM",
                           "trinity_mini_tiny.conf")) as f:
        text = f.read()
    assert trinity_mini_tiny(batch_size=4) in text
    conf = tmp_path / "tiny.conf"
    conf.write_text(text.replace("path_tokens = train.tok", "path_tokens = %s"
                                 % (tmp_path / "train.tok")))
    stream = tmp_path / "run.jsonl"
    assert main([str(conf), "monitor=jsonl", "monitor_path=%s" % stream,
                 "num_round=2", "max_round=2", "silent=1",
                 "model_dir=%s" % (tmp_path / "models")]) == 0
    recs = read_jsonl(str(stream))
    validate_records(recs)
    losses = [r["loss"] for r in recs if r["event"] == "step"]
    assert len(losses) >= 6 and all(np.isfinite(losses))
    assert np.mean(losses[-2:]) < 0.5 * losses[0]
    assert all(r["dropped"] == 0 for r in recs if r["event"] == "moe")
    (layout,) = [r for r in recs if r["event"] == "layout"][:1]
    assert layout["attention_window_layers"] == 4
