"""LFM2's block as a model (models/lfm2.py) against its plain reference
(cxxnet_tpu/reference/lfm2_24b_a2b.py): the builder's pattern; the whole
tiny model's loss, gradients and two Adam steps through ``NetTrainer``;
the head tied to the embedding (one matrix in the tree, the checkpoint and
Adam's state); the shares of its expert layer, which has no shared expert,
adding up to the uncut reference's; the records that count the new layer;
the CLI; the FLOPs and parameters of the benchmark's cut. The layers one
at a time are tests/test_lfm2_layers.py's.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.graph import NetGraph
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import seq_shape
from cxxnet_tpu.models import lfm2_24b_a2b, lfm2_tiny
from cxxnet_tpu.models.lfm2 import PUBLISHED_LAYER_TYPES
from cxxnet_tpu.nnet.net import FuncNet
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.reference import lfm2_24b_a2b as ref
from cxxnet_tpu.utils.config import parse_config

from test_lfm2_layers import D, ROOT, T, TINY, _close, _layer, _x

CUT = ("conv", "full_attention", "conv", "conv", "conv")


def _trainer(dtype="float32", held=(2, 4), extra=()):
    t = NetTrainer(parse_config(lfm2_tiny(
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


# -- the builder -----------------------------------------------------------------


def test_the_builder_follows_layer_types_and_the_dense_count():
    """The published pattern (attention at 2, 6, ..., 38 of 40; two
    leading dense layers), the cell's cut of it, and what the builder
    refuses."""
    assert len(PUBLISHED_LAYER_TYPES) == 40
    assert [i for i, k in enumerate(PUBLISHED_LAYER_TYPES)
            if k == "full_attention"] == list(range(2, 40, 4))
    assert tuple(ref.PUBLISHED["layer_types"]) == PUBLISHED_LAYER_TYPES
    assert tuple(PUBLISHED_LAYER_TYPES[1:6]) == CUT
    g = NetGraph()
    g.configure(parse_config(lfm2_24b_a2b()))
    kinds = [l.type for l in g.layers]
    assert kinds.count("gated_conv") == 30
    assert kinds.count("gqa_attention") == 10
    assert kinds.count("swiglu") == 2 and kinds.count("moe") == 38
    assert kinds.count("share") == 1 and "fullc" not in kinds
    mixers = [l.name for l in g.layers
              if l.type in ("gated_conv", "gqa_attention")]
    assert mixers[:4] == ["l0_conv", "l1_conv", "l2_attn", "l3_conv"]
    assert [l.name for l in g.layers if l.type == "swiglu"] == [
        "l0_mlp", "l1_mlp"]
    t = _trainer()
    assert [type(l).__name__ for l in t.net.layer_objs
            if hasattr(l, "conv_kernel") or hasattr(l, "fused_core")] == [
        "GatedConvLayer", "GQAAttentionLayer"] + ["GatedConvLayer"] * 3
    assert [type(l).__name__ for l in t.net.layer_objs
            if type(l).__name__ in ("SwiGLULayer", "MoELayer")] == [
        "SwiGLULayer"] + ["MoELayer"] * 4
    attn = next(l for l in t.net.layer_objs if hasattr(l, "fused_core"))
    assert (attn.gate, attn.rope, attn.window, attn.rope_dim) == (0, 1, 0, 0)
    moe = next(l for l in t.net.layer_objs if hasattr(l, "grouped"))
    assert (moe.nshared, moe.score_func, moe.norm_topk, moe.scale) == (
        0, "sigmoid", 1, 1.0)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_tiny(layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="dense_layers"):
        lfm2_tiny(layer_types=("conv",), dense_layers=2)


# -- the whole tiny model --------------------------------------------------------


def test_tiny_model_loss_and_gradients_match_the_reference():
    t = _trainer()
    data, lab = _batch()
    params, biases = _host(t)
    assert "head" not in params and set(biases) == {
        "l%d_moe" % i for i in range(1, 5)}
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
        assert set(g[lk]) == set(g_ref[lk]), lk
        for tag in g[lk]:
            _close(g[lk][tag], g_ref[lk][tag], 5e-5)
    # the reference's memory bounds change no value
    l_plain = ref.loss(params, biases, jnp.asarray(data),
                       jnp.asarray(lab, jnp.int32), TINY, held=(2, 4))
    assert abs(float(l_plain) - float(l_ref)) < 1e-6


_REFERENCE_STEPS = {}


def _reference_two_steps(params, biases, data, lab):
    """The reference's two Adam steps, made once for both dtypes (the
    seeded start is the same float32 masters)."""
    if not _REFERENCE_STEPS:
        after, losses = jax.jit(lambda p: ref.train_steps(
            p, biases, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY,
            2, lr=0.01, held=(2, 4)))(params)
        _REFERENCE_STEPS.update(after=after, losses=losses, start=params)
    for k, v in _REFERENCE_STEPS["start"].items():
        for tag in v:
            assert np.array_equal(v[tag], params[k][tag])
    return _REFERENCE_STEPS["after"], _REFERENCE_STEPS["losses"]


@pytest.mark.parametrize("dtype,tol_loss,tol_step", [
    ("float32", 1e-4, 2e-2), ("bfloat16", 0.03, 0.8)])
def test_two_adam_steps_through_run_steps_match_the_reference(
        dtype, tol_loss, tol_step):
    """As the siblings': the second step's loss and the parameters after
    it, as a share of how far the reference moved (bfloat16 at toy widths
    and sigma 0.3 reads well under the 1 of a state left unchanged; the
    published widths' reading is the chip's, PERF.md)."""
    t = _trainer(dtype)
    data, lab = _batch()
    params, biases = _host(t)
    t.run_steps(DataBatch(data=data, label=lab), 2)
    after, losses = _reference_two_steps(params, biases, data, lab)
    assert abs(t.last_loss - float(losses[1])) < tol_loss * float(losses[1])
    assert float(losses[1]) < float(losses[0])
    num = sum(float(jnp.sum((t.params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    den = sum(float(jnp.sum((params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    assert (num / den) ** 0.5 < tol_step
    assert t.update_counter == 2


# -- the tied head ---------------------------------------------------------------


def test_the_head_is_the_embeddings_matrix_once_in_every_tree(tmp_path):
    """One ``(rows held, hidden)`` matrix in the parameters, in Adam's
    state and in the checkpoint; its gradient is the sum of the lookup's
    and the head's; a checkpoint round trip brings back the same
    logits."""
    t = _trainer(extra=[("save_optimizer", "1")])
    assert t.params["embed"]["wmat"].shape == (64, D)
    assert "head" not in t.params and "head" not in t.opt_state
    assert set(t.opt_state["embed"]) == {"wmat"}
    (li,) = [i for i, l in enumerate(t.graph.layers) if l.type == "share"]
    assert t.graph.effective_type(li) == "embed"
    assert t.net.layer_scope(li) == "embed.head"
    assert t.net.layer_objs[li] is t.net.layer_objs[0]
    assert t.net.node_shapes[t.graph.layers[li].nindex_out[0]] \
        == seq_shape(T, 64)
    data, lab = _batch()
    net, e = t.net, t.params["embed"]["wmat"]

    def loss(rows, head):
        # the program with the head's read handed a second array
        outs, _, logits = net.forward(
            dict(t.params, embed={"wmat": rows}), t.net_state,
            jnp.asarray(data), is_train=True, collect_logits=True,
            keep_nodes=(t.graph.layers[li].nindex_in[0],))
        h = outs[t.graph.layers[li].nindex_in[0]]
        lg = jnp.einsum("btd,vd->btv", h, head)
        return net.layer_objs[-1].loss_value(lg, jnp.asarray(lab), None)

    with jax.default_matmul_precision("highest"):
        g_rows, g_head = jax.grad(loss, argnums=(0, 1))(e, e)
        g_tied = jax.grad(lambda p: net.loss_fn(
            p, t.net_state, jnp.asarray(data), jnp.asarray(lab), None)[0])(
                t.params)["embed"]["wmat"]
    assert float(jnp.abs(g_rows).max()) > 1e-4
    assert float(jnp.abs(g_head).max()) > 1e-4
    _close(g_tied, g_rows + g_head, 1e-5)
    # a checkpoint round trip: one tensor a tree, the same step after it
    t.run_steps(DataBatch(data=data, label=lab), 2)
    path = str(tmp_path / "lfm2.model")
    t.save_model(path)
    from cxxnet_tpu.nnet.checkpoint import read_snapshot
    blob, _ = read_snapshot(path)
    assert "param/embed/wmat" in blob
    assert not [k for k in blob if "/head/" in k]
    assert len([k for k in blob if k.startswith("opt/embed/")]) == len(
        t.opt_state["embed"]["wmat"])
    back = NetTrainer(parse_config(lfm2_tiny(experts_held=4, expert_first=2))
                      + [("dtype", "float32"), ("seed", "9"), ("silent", "1"),
                         ("save_optimizer", "1")])
    back.load_model(path)
    assert back.update_counter == 2
    for k in t.params:
        for tag in t.params[k]:
            assert np.array_equal(np.asarray(t.params[k][tag]),
                                  np.asarray(back.params[k][tag])), (k, tag)
    t.run_steps(DataBatch(data=data, label=lab), 1)
    back.run_steps(DataBatch(data=data, label=lab), 1)
    assert back.last_loss == pytest.approx(t.last_loss, rel=1e-6)


# -- the records -----------------------------------------------------------------


def test_records_count_the_short_convolutions_and_the_tied_head():
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
    assert (layout["short_conv_layers"], layout["head_tied"]) == (4, True)
    assert (layout["attention_layers"], layout["attention_fused_layers"],
            layout["attention_window_layers"]) == (1, 0, 0)
    assert (layout["moe_layers"], layout["moe_grouped_layers"]) == (4, 0)
    # gated_conv's convolution is the shared XLA function, never
    # gated_delta's kernel
    assert (layout["linear_attention_layers"],
            layout["linear_attention_fused_conv_layers"]) == (0, 0)
    (info,) = [r for r in sink.records if r["event"] == "model_info"]
    assert info["params"] == sum(int(np.prod(w.shape))
                                 for pt in t.params.values()
                                 for w in pt.values())
    (scopes,) = [r for r in sink.records if r["event"] == "program_scopes"]
    paths = set(scopes["scopes"].values())
    for want in ("gated_conv.l0_conv", "gated_conv.l4_conv",
                 "gqa_attention.l1_attn", "swiglu.l0_mlp", "moe.l1_moe",
                 "moe.l4_moe", "rmsnorm.l2_op_norm", "embed.embed",
                 "embed.head"):
        assert any(want in p for p in paths), want
    for part in ("in_proj", "short_conv", "out_proj"):
        assert any("gated_conv.l2_conv" in p and part in p.split(
            "gated_conv.l2_conv")[1] for p in paths), part
    assert any("gqa_attention.l1_attn" in p and "core" in p.split(
        "gqa_attention.l1_attn")[1] for p in paths)
    # no expert layer of this model opens a ``shared`` part
    assert not any(p.rstrip(")").endswith("shared") for p in paths)
    moes = [r for r in sink.records if r["event"] == "moe"]
    assert moes and moes[0]["dropped"] == 0
    assert set(moes[0]["layers"]) == {"l%d_moe" % i for i in range(1, 5)}
    # a net without such layers counts none and ties nothing
    from cxxnet_tpu.models import qwen3_next_tiny
    plain = NetTrainer(parse_config(qwen3_next_tiny()) + [("silent", "1")])
    plain.init_model()
    sink2 = MemorySink()
    plain.set_monitor(Monitor(sink2))
    (rec,) = [r for r in sink2.records if r["event"] == "layout"]
    assert (rec["short_conv_layers"], rec["head_tied"]) == (0, False)


def test_records_count_the_layers_the_kernels_take():
    """The same block at an attention head of 64 over 128 positions and
    experts of 128 x 128 in blocks of 128: the attention layer takes the
    fused core (1 of 1), the expert layers the grouped kernels (2 of 2),
    and the step trains through them (interpreted here)."""
    from cxxnet_tpu.models.lfm2 import lfm2_lm
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    t = NetTrainer(parse_config(lfm2_lm(
        vocab=64, hidden=128, layer_types=("conv", "full_attention", "conv"),
        dense_layers=1, dense_width=128, nhead=2, nkvhead=1, head_dim=64,
        rope_theta=1e6, conv_kernel=3, norm_eps=1e-5, expert_width=128,
        num_experts=8, experts_per_tok=2, routed_scaling_factor=1.0,
        experts_held=4, expert_first=2, seq_len=128, batch_size=2,
        q_block=128, expert_block=128, loss_chunk=64, bias_sigma=0.1,
        init_sigma=0.1, lr=0.01))
        + [("dtype", "bfloat16"), ("seed", "3"), ("silent", "1")])
    t.init_model()
    assert [l.fused_core for l in t.net.layer_objs
            if hasattr(l, "fused_core")] == [True]
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    ids = np.random.RandomState(0).randint(0, 64, (2, 129))
    b = DataBatch(data=ids[:, :128].astype(np.int32),
                  label=ids[:, 1:].astype(np.float32))
    t.run_steps(b, 2)
    first = t.last_loss
    t.run_steps(b, 2)
    assert np.isfinite(first) and t.last_loss < first
    validate_records(sink.records)
    (layout,) = [r for r in sink.records if r["event"] == "layout"]
    assert (layout["attention_layers"], layout["attention_fused_layers"],
            layout["attention_saved_layers"]) == (1, 1, 1)
    assert (layout["moe_layers"], layout["moe_grouped_layers"]) == (2, 2)
    assert (layout["short_conv_layers"], layout["head_tied"]) == (2, True)


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
""" % (tmp_path / "train.tok", lfm2_tiny(batch_size=4),
       tmp_path / "models"))
    stream = tmp_path / "run.jsonl"
    assert main([str(conf), "monitor=jsonl",
                 "monitor_path=%s" % stream]) == 0
    recs = read_jsonl(str(stream))
    validate_records(recs)
    losses = [r["loss"] for r in recs if r["event"] == "step"]
    assert len(losses) >= 6 and all(np.isfinite(losses))
    assert np.mean(losses[-2:]) < 0.5 * losses[0]
    assert all(r["dropped"] == 0 for r in recs if r["event"] == "moe")


# -- a chip's share of the block -------------------------------------------------


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_reference():
    """The guide's share test for this family's expert half, at the
    deployment's own counts: top-4 of 64 experts over eight shares of 8.
    The layer has no shared expert, so nothing is counted once: the eight
    shares' results simply add up to the uncut reference's layer."""
    cfg = dict(TINY, num_experts=64, num_experts_per_tok=4)
    moe_cfg = dict(nexpert=64, topk=4, nhidden=24, nshared=0, expert_block=4,
                   routed_scaling_factor=1, norm_topk_prob=1, bias_seed=2,
                   bias_sigma=0.5, init_sigma=0.3)
    z = _x(7)
    full, p, st = _layer("moe", moe_cfg, seq_shape(T, D))
    assert not {"sgate", "sup", "sdown"} & set(p)
    bias = st["bias"]
    with jax.default_matmul_precision("highest"):
        flat = z.reshape(-1, D)
        uncut = ref.moe(p, bias, flat, cfg, None, None)
        total, loads = jnp.zeros_like(uncut), []
        for share in range(8):
            layer, _, _ = _layer("moe", dict(moe_cfg,
                                             expert_first=8 * share,
                                             expert_count=8),
                                 seq_shape(T, D))
            mine = {k: (v[8 * share:8 * share + 8]
                        if k in ("egate", "eup", "edown") else v)
                    for k, v in p.items()}
            (out,), st2 = layer.forward(mine, st, [z], True, None)
            total = total + out.reshape(-1, D)
            loads.append(int(st2["picks_held"]))
            assert int(st2["dropped"]) == 0
            _close(out.reshape(-1, D), ref.moe(mine, bias, flat, cfg,
                                               (8 * share, 8), None))
        _close(total, uncut)
        (whole,), _ = full.forward(p, st, [z], True, None)
        _close(whole.reshape(-1, D), uncut)
    assert sum(loads) == 2 * T * 4          # every pick lands on one share
    assert float(jnp.abs(uncut).max()) > 1e-3
    # the bias chooses and does not weigh: without it other experts win
    other = ref.moe(p, jnp.zeros_like(bias), flat, cfg, None, None)
    assert float(jnp.abs(other - uncut).max()) > 1e-3


# -- the benchmark's cut ---------------------------------------------------------


def test_analytic_flops_and_parameters_of_the_cut_configuration():
    """The count the MFU metric divides by, at the benchmark's sizes
    (published layers 1-5: a dense conv layer, then a period; 8 of 64
    experts; 8,192 rows), from shapes alone, and ISSUE 38's table of
    parameters, tensor by tensor: 469,284,992."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_24b_a2b.conf")) as f:
        text = f.read()
    # the conf is the zoo builder's text
    assert text == lfm2_24b_a2b(layer_types=CUT, dense_layers=1, vocab=8192,
                                experts_held=8)
    with open(os.path.join(ROOT, "benchmarks", "reference",
                           "lfm2_24b_a2b.py")) as f, \
            open(os.path.join(ROOT, "cxxnet_tpu", "reference",
                              "lfm2_24b_a2b.py")) as g:
        assert f.read() == g.read()
    g = NetGraph()
    g.configure(parse_config(text) + [("dtype", "bfloat16")])
    net = FuncNet(g, 2)
    t, d = 8192, 2048
    conv = 2 * d * 3 * d + 2 * d * d + 2 * 3 * d
    attn_proj = 2 * (2 * d * d + 2 * d * 512)
    core = 4 * 32 * 64 * (t * (t + 1) / 2)
    dense = 6 * d * 11776
    moe = 2 * d * 64 + 6 * d * 1536 * 4 * 8 / 64
    want = t * (4 * conv + attn_proj + dense + 4 * moe + 2 * d * 8192) + core
    assert net.analytic_flops_per_example() == pytest.approx(want, rel=1e-9)
    # ISSUE 38's reckoning: 203 M multiply-accumulates a token forward,
    # 1.22 GFLOP a token trained; the conv mixers a third of it
    assert 202e6 < want / t / 2 < 204e6 and 1.21e9 < 3 * want / t < 1.23e9
    assert 0.32 < 4 * conv / (want / t) < 0.34
    assert 0.08 < (core / t) / (want / t) < 0.09
    assert net.tokens_per_example == t
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))[0]
    count = lambda keys: sum(int(np.prod(w.shape)) for k in keys
                             for w in shapes[k].values())
    for i in (0, 2, 3, 4):
        assert count(["l%d_conv" % i]) == 16783360
    assert count(["l1_attn"]) == 10485888 and "wg" not in shapes["l1_attn"]
    assert count(["l0_mlp"]) == 72351744
    for i in (1, 2, 3, 4):
        assert count(["l%d_moe" % i]) == 75497472 + 2048 * 64
        assert set(shapes["l%d_moe" % i]) == {"router", "egate", "eup",
                                              "edown"}
    norms = [k for k in shapes if k.endswith("_norm")]
    assert len(norms) == 11 and count(norms) == 11 * 2048
    assert 4 * 2048 * 64 + count(norms) == 546816
    assert count(["embed"]) == 16777216 and "head" not in shapes
    assert count(shapes) == 469284992
    # the kernels take every layer at these shapes
    attn = [l for l in net.layer_objs if hasattr(l, "fused_core")]
    assert len(attn) == 1 and attn[0].fused_core and attn[0].head_dim == 64
    moes = {id(l): l for l in net.layer_objs if hasattr(l, "grouped")}
    assert len(moes) == 4 and all(l.grouped for l in moes.values())
    assert all(l.budget(2 * t) == 56 for l in moes.values())
