"""The fused causal-attention kernel (layers/pallas_kernels.py) against
the blocked XLA core it replaces where the shapes tile
(layers/sequence.py: causal_attention), in interpret mode: the kernel's
values and gradients, the shape gate, and an attention layer and a whole
trainer taking each path. That the kernels compile for the chip at the
language-model cell's shapes is tests/test_chip_compile.py's job.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import create_layer, pallas_kernels as pk, seq_shape
from cxxnet_tpu.layers.sequence import causal_attention
from cxxnet_tpu.models.kimi_vl import decoder_lm
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config

B, H, T, D_NOPE, D_ROPE, D_V = 2, 2, 512, 128, 64, 128
SCALE = 1.0 / np.sqrt(D_NOPE + D_ROPE)


def _operands(dtype):
    """(q_nope, q_rope, k_nope, k_rope, v), the one k_rope shared by the
    heads as MLA's is, and a cotangent for the output."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shapes = [(B, H, T, D_NOPE), (B, H, T, D_ROPE), (B, H, T, D_NOPE),
              (B, 1, T, D_ROPE), (B, H, T, D_V), (B, H, T, D_V)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(keys, shapes)]


def _xla(qn, qr, kn, kr, v):
    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, qr.shape)], axis=-1)
    return causal_attention(q, k, v, SCALE, 128)


def _fused(bq, bk):
    def f(qn, qr, kn, kr, v):
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        return pk._attention((flat(qn), flat(qr)), (flat(kn), flat(kr)),
                             flat(v), SCALE, bq, bk, 0).reshape(v.shape)
    return f


def _value_and_grads(f, args, w):
    return jax.value_and_grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w.astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))(*args)


# Unequal tiles, so that tiles below the diagonal (no mask), tiles the
# diagonal crosses (masked) and tiles above it (skipped, and whose index
# maps repeat the diagonal's block) all occur, in both kernels' grids.
# float32: the two cores are the same function, and differ by the order
# of the row sum and of the tiles' accumulation only (1e-5 of the largest
# value). bfloat16: each core rounds its probabilities (the XLA core after
# dividing by the row sum, the kernel before), its output and its
# gradients to bfloat16 once, 2^-8 relative each: 1 % of the largest
# value holds both.
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128)])
def test_fused_kernel_matches_the_xla_core(bq, bk, dtype, tol):
    *args, w = _operands(dtype)
    want_loss, want = _value_and_grads(_xla, args, w)
    got_loss, got = _value_and_grads(_fused(bq, bk), args, w)
    out, ref = _fused(bq, bk)(*args), _xla(*args)
    assert out.dtype == ref.dtype and out.shape == ref.shape
    for name, a, b in [("o", out, ref)] + [
            ("d" + n, g, r) for n, g, r in
            zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"), got, want)]:
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert a.shape == b.shape, name
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), \
            (name, np.abs(a - b).max(), np.abs(b).max())
    assert abs(float(got_loss) - float(want_loss)) \
        <= tol * abs(float(want_loss)) + tol


def test_the_public_call_takes_its_tiles_from_the_shapes():
    *args, _ = _operands("float32")
    qn, qr, kn, kr, v = args
    got = pk.causal_attention((qn, qr), (kn, kr), v, SCALE, q_block=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_xla(*args)),
                               atol=2e-5)
    assert pk._attn_tiles(T, 256) == (256, 512)
    assert pk._attn_tiles(8192, 1024) == (1024, 1024)
    assert pk._attn_tiles(8192, 0) == (1024, 1024)
    assert pk._attn_tiles(8192, 512) == (512, 1024)
    assert pk._attn_tiles(384, 1024) == (128, 128)
    assert pk._attn_tiles(16, 8) == (0, 0)


@pytest.mark.parametrize("time,q_block,qk,v,fits", [
    (8192, 1024, (128, 64), 128, True),     # the language-model cell
    (8192, 0, (128, 64), 128, True),        # no cap on the query tile
    (16, 8, (8, 4), 8, False),              # the tiny model
    (8192, 1024, (8, 4), 8, False),         # its widths at the cell's length
    (8200, 1024, (128, 64), 128, False),    # no tile divides the length
    (8192, 64, (128, 64), 128, False),      # q_block under the smallest tile
    (8192, 1024, (128, 64), 64, True),      # values of one half-lane
    (8192, 1024, (128, 64), 32, False),     # ... but no narrower
    (8192, 1024, (128, 64), 192, False),    # ... and no lane and a half
    (32768, 1024, (128, 64), 128, False),   # dQ of a sequence outgrows VMEM
])
def test_applicable_is_a_function_of_the_shapes(time, q_block, qk, v, fits):
    assert pk.causal_attention_applicable(time, q_block, qk, v) is fits


ATTN = dict(nhead=2, qk_nope_head_dim=D_NOPE, qk_rope_head_dim=D_ROPE,
            v_head_dim=D_V, kv_lora_rank=32, rope_theta=800000.0, eps=1e-5,
            q_block=128, init_sigma=0.1)


def _attention_layer(time, **over):
    layer = create_layer("mla_attention", [
        (k, str(v)) for k, v in dict(ATTN, **over).items()])
    layer.infer_shape([seq_shape(time, 64)])
    return layer


def test_the_layer_takes_the_path_its_shapes_allow():
    """Tiling shapes: the kernel, under the layer's ``core`` scope; the
    same layer held to the XLA core gives the same values and gradients
    (float32: 1e-4 of the largest, the projections around the core
    included). The tiny model's widths and length: the XLA core."""
    assert not _attention_layer(16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                                v_head_dim=8, q_block=4).fused_core
    assert not _attention_layer(200).fused_core
    layer = _attention_layer(256)
    assert layer.fused_core and layer.sub_scopes == ("core",)
    params = layer.init_params(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 64))

    def loss(p, x):
        (y,), _ = layer.forward(p, {}, [x], True, None)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    text = jax.jit(jax.grad(loss)).lower(params, x).as_text(debug_info=True)
    assert "core" in text
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        layer.fused_core = False
        want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= 1e-4 * max(1.0, np.abs(b).max())


def _lm_trainer(seq_len, nope, rope, vdim, q_block):
    conf = decoder_lm(
        vocab=32, hidden=32, num_layers=2, first_k_dense=1, nhead=2,
        qk_nope_head_dim=nope, qk_rope_head_dim=rope, v_head_dim=vdim,
        kv_lora_rank=16, rope_theta=800000.0, rms_norm_eps=1e-5,
        dense_width=48, expert_width=24, n_routed_experts=4,
        experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2.0,
        experts_held=4, expert_first=0, seq_len=seq_len, batch_size=2,
        q_block=q_block, expert_block=8, loss_chunk=64, bias_sigma=0.01,
        init_sigma=0.1, lr=0.01)
    t = NetTrainer(parse_config(conf) + [("silent", "1"), ("seed", "3")])
    t.init_model()
    return t


def _layout(t):
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    (rec,) = [r for r in sink.records if r["event"] == "layout"]
    return rec


def test_the_layout_record_counts_the_fused_layers_and_a_step_agrees():
    """A two-layer decoder whose shapes tile runs the kernel in both
    attention layers, through ``remat = block`` and the trainer's step;
    the same net held to the XLA core reads the same loss after two Adam
    steps (float32: the cores differ by the order of their sums). A net
    of the tiny model's widths counts its layers and fuses none."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.monitor.schema import validate_record
    tiny = _layout(_lm_trainer(16, 8, 4, 8, 8))
    assert (tiny["attention_layers"], tiny["attention_fused_layers"]) == (2, 0)
    fused, plain = (_lm_trainer(128, D_NOPE, D_ROPE, D_V, 128)
                    for _ in range(2))
    for layer in plain.net.layer_objs:
        if hasattr(layer, "fused_core"):
            layer.fused_core = False
    rec, rec_plain = _layout(fused), _layout(plain)
    assert not validate_record(rec)
    assert (rec["attention_layers"], rec["attention_fused_layers"]) == (2, 2)
    assert rec_plain["attention_fused_layers"] == 0
    assert rec["pallas_interpret"] is True
    assert "core" in fused.net.scope_names
    ids = np.random.RandomState(0).randint(0, 32, (2, 129))
    batch = DataBatch(data=ids[:, :-1].astype(np.int32),
                      label=ids[:, 1:].astype(np.float32))
    losses = []
    for t in (fused, plain):
        with jax.default_matmul_precision("highest"):
            t.update(batch)
            t.update(batch)
        losses.append(t.last_loss)
    assert np.isfinite(losses[0]) and abs(losses[0] - losses[1]) \
        <= 1e-4 * abs(losses[1])


# -- grouped key/value heads and a window (PR 32) ------------------------------

G_H, G_D = 4, 128


def _grouped_operands(h_kv, dtype="float32"):
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    shapes = [(1, G_H, T, G_D), (1, h_kv, T, G_D), (1, h_kv, T, G_D),
              (1, G_H, T, G_D)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(keys, shapes)]


def _grouped_pair(window, bq, bk):
    """(kernel, XLA core) over (q, k, v) with ``k`` and ``v`` a head a
    group; the XLA core sees each key/value head repeated beside the
    query heads of its group, so its gradient sums over them."""
    scale = G_D ** -0.5

    def fused(q, k, v):
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        return pk._attention((flat(q),), (flat(k),), flat(v), scale, bq, bk,
                             0 if window >= T else window).reshape(q.shape)

    def xla(q, k, v):
        each = lambda a: jnp.repeat(a, G_H // a.shape[1], axis=1)
        return causal_attention(q, each(k), each(v), scale, 128, window)

    return fused, xla


# 256: the band's far edge lies on a tile's edge; 200: inside a tile (and
# inside the query tile's own span at 256 x 128); 72: narrower than a
# tile; 1000: wider than the sequence, which is no window. Unequal tiles
# both ways, so that the grids' shortened inner axes, their clamped index
# maps and the three kinds of tile (inside the band, crossed by an edge,
# outside) all occur in both kernels.
@pytest.mark.parametrize("h_kv", [1, 2, 4])
@pytest.mark.parametrize("window,bq,bk", [
    (256, 128, 128), (200, 256, 128), (200, 128, 256), (72, 128, 128),
    (1000, 128, 256), (0, 256, 128)])
def test_grouped_heads_and_window_match_the_xla_core(window, bq, bk, h_kv):
    *args, w = _grouped_operands(h_kv)
    fused, xla = _grouped_pair(window, bq, bk)
    grads = lambda f: jax.grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(*args)
    got, want = grads(fused), grads(xla)
    for name, a, b in [("o", fused(*args), xla(*args))] + [
            ("d" + n, g, r) for n, g, r in zip("qkv", got, want)]:
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert a.shape == b.shape, name
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max(), \
            (name, np.abs(a - b).max(), np.abs(b).max())


def test_the_window_is_the_inequality_and_shortens_the_grids():
    """Against the mask written out over the whole square, and the
    grids' inner axes as long as the band is wide: at the cell's shapes
    3 of 8 key tiles a query tile, 3 of 8 query tiles a key tile."""
    q, k, v, _ = _grouped_operands(2)
    window = 200
    got = pk.causal_attention((q,), (k,), v, G_D ** -0.5, 128, window)
    each = lambda a: jnp.repeat(a, G_H // a.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, each(k)) * G_D ** -0.5
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    s = jnp.where((0 <= i - j) & (i - j < window), s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), each(v))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert pk._band_tiles(8192, 1024, 1024, 2048) == (3, 3)
    assert pk._band_tiles(8192, 1024, 1024, 0) == (8, 8)
    assert pk._band_tiles(8192, 512, 1024, 2048) == (3, 6)
    assert pk._band_tiles(512, 128, 128, 256) == (3, 3)
    assert pk._band_tiles(512, 128, 128, 257) == (3, 3)
    assert pk._band_tiles(512, 128, 128, 258) == (4, 4)
    assert pk._band_tiles(512, 256, 128, 72) == (3, 2)


# sha256 over the float32 bytes of (o, dq_nope, dq_rope, dk_nope, dk_rope,
# dv) of MLA's call at this file's operands, computed on the tree before
# grouped heads and the window (e9a9fc5): the kernel's window = 0 path is
# that tree's, to the bit.
@pytest.mark.parametrize("dtype,digest", [
    ("float32",
     "25900c99e47ca48bb064e0f785b13b1aed0de0968f94999f9e0b5912f43db91e"),
    ("bfloat16",
     "72031130acf62a46575137f88feac96029b8022b78a61f1d27d0b17adff94494")])
def test_mlas_call_is_unchanged_to_the_bit(dtype, digest):
    import hashlib
    *args, w = _operands(dtype)
    f = lambda qn, qr, kn, kr, v: pk.causal_attention(
        (qn, qr), (kn, kr), v, SCALE, 256)
    _, grads = _value_and_grads(f, args, w)
    h = hashlib.sha256()
    for x in (f(*args),) + tuple(grads):
        h.update(np.asarray(x.astype(jnp.float32)).tobytes())
    assert h.hexdigest() == digest


@pytest.mark.parametrize("nhead,nkvhead,window,fits", [
    (32, 4, 2048, True),      # the cell's sliding layers
    (32, 4, 0, True),         # its full layer
    (32, 32, 2048, True),     # a key/value head a query head
    (32, 1, 0, True),         # one for all
    (32, 5, 0, False),        # groups of unequal size
    (32, 4, -1, False),
])
def test_applicable_takes_head_groups_and_windows(nhead, nkvhead, window,
                                                 fits):
    assert pk.causal_attention_applicable(
        8192, 1024, (128,), 128, nhead, nkvhead, window) is fits


def test_gqa_layer_takes_the_kernel_where_its_shapes_tile():
    """A ``gqa_attention`` layer at widths that tile runs the kernel
    under its ``core`` scope, window and groups included; held to the XLA
    core it gives the same values and gradients."""
    layer = create_layer("gqa_attention", [(k, str(v)) for k, v in dict(
        nhead=4, nkvhead=2, head_dim=128, window=200, rope=1, eps=1e-5,
        q_block=128, init_sigma=0.1).items()])
    layer.infer_shape([seq_shape(256, 64)])
    assert layer.fused_core and layer.sub_scopes == ("core",)
    params = layer.init_params(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 64))

    def loss(p, x):
        (y,), _ = layer.forward(p, {}, [x], True, None)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        layer.fused_core = False
        want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= 1e-4 * max(1.0, np.abs(b).max())
