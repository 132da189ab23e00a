"""Mellum2's layers (layers/sequence.py: ``gqa_attention``'s YaRN rope type
beside a window, QK norm and no gate) against the plain reference
(cxxnet_tpu/reference/mellum2_12b_a2_5b.py) and against a transcription of
``transformers``' YaRN formula, and what was there before to the bit. The
whole model is tests/test_mellum2_model.py's; the expert axis
tests/test_expert_parallel.py's.
"""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import create_layer, seq_shape
from cxxnet_tpu.layers.sequence import rope_tables, yarn_frequencies
from cxxnet_tpu.models.mellum2 import PUBLISHED_YARN
from cxxnet_tpu.reference import mellum2_12b_a2_5b as ref

T, D = 16, 32
# the tiny twin's widths (models/mellum2.py: mellum2_tiny)
TINY = dict(
    vocab_size=64, hidden_size=D, num_hidden_layers=4,
    layer_types=("sliding_attention",) * 3 + ("full_attention",),
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    sliding_window=6, rms_norm_eps=1e-6, moe_intermediate_size=24,
    num_experts=8, num_experts_per_tok=3, norm_topk_prob=True,
    rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 100,
                           "factor": 4, "original_max_position_embeddings": 64,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}})


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(jnp.asarray(a).astype(jnp.float32)).tobytes())
    return h.hexdigest()


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


def _transformers_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """``_compute_yarn_parameters`` of transformers (truncate true) as its
    source reads, in float64: (inv_freq, low, high)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    linear = (np.arange(dim // 2, dtype=np.float64) - low) / (high - low)
    extrapolation_factor = 1 - np.clip(linear, 0, 1)
    inv_freq = (inv_freq_interpolation * (1 - extrapolation_factor)
                + inv_freq_extrapolation * extrapolation_factor)
    return inv_freq, low, high


def test_yarn_tables_match_transformers_formula_at_the_published_keys():
    """The ramp runs from pair 18 to pair 35 of 64; the fast pairs keep
    theta^(-2i/128), the slow ones are divided by 16; cos and sin carry
    attention_factor 1.27726 = 0.1 ln 16 + 1."""
    y = PUBLISHED_YARN
    inv, low, high = _transformers_yarn(
        128, y["rope_theta"], y["factor"],
        y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"])
    assert (low, high) == (18, 35)
    assert y["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1)
    got = yarn_frequencies(128, y["rope_theta"], y["factor"],
                           y["original_max_position_embeddings"],
                           y["beta_fast"], y["beta_slow"])
    _close(got, inv, 1e-6)
    plain = 1.0 / y["rope_theta"] ** (np.arange(0, 128, 2) / 128)
    assert np.allclose(np.asarray(got)[:18], plain[:18], rtol=1e-6)
    assert np.allclose(np.asarray(got)[35:], plain[35:] / 16, rtol=1e-6)
    keys = (y["factor"], y["original_max_position_embeddings"],
            y["beta_fast"], y["beta_slow"], y["attention_factor"])
    for t in (64, 8192):
        cos, sin = rope_tables(t, 128, y["rope_theta"], keys)
        ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
        # float32 angles: a position of 8,191 turns the fastest pair by
        # 8,191 radians, whose float32 rounding is ~5e-4 of a radian
        tol = 1e-5 if t == 64 else 2e-3
        _close(cos, y["attention_factor"] * np.cos(ang), tol)
        _close(sin, y["attention_factor"] * np.sin(ang), tol)
    # the reference's own transcription agrees
    _close(ref.yarn_inv_freq(128, ref.PUBLISHED["rope_parameters"][
        "full_attention"]), inv, 1e-6)


def test_default_rope_tables_and_layers_are_the_parents_to_the_bit():
    """sha256 over the float32 bytes of the default tables, and of (y,
    every gradient) of a windowed gated and a full ungated
    ``gqa_attention`` and of two ``moe`` layers, made on the parent commit
    (2b93cad) with the same seeds: the default rope type and no expert
    axis leave everything as it was."""
    tables = [x for t, d, th in ((16, 8, 1e4), (8192, 128, 5e5),
                                 (64, 64, 1e6))
              for x in rope_tables(t, d, th)]
    assert _digest(tables) == \
        "90c270cc33a1e323a84764aa855179b665e2581bbb0cf44a1bdd5d0b2f01dea6"
    want = {
        ("gqa_attention", 1): "f5a20569bf0d8530aae612cd6702147dfe37950616"
                              "dedc2d4ecf02671a96f78d",
        ("gqa_attention", 0): "680b8e6cdcdb46dd43210f1c8572f14a51e3c65091"
                              "78228f73bf404abd585fae",
        ("moe", 0): "b291c4959d34bc39dc81d33ef8b9a5e74815d67de63030d32772d2"
                    "db6a3eea3a",
        ("moe", 1): "121c8eaac616347e30c9dcb24d59c66e5b3403fc44659ebfeb0993"
                    "b2171a2a30"}
    cfgs = {
        ("gqa_attention", 1): dict(nhead=4, nkvhead=2, head_dim=8, rope=1,
                                   rope_theta=1e6, q_block=8, gate=1,
                                   window=6),
        ("gqa_attention", 0): dict(nhead=4, nkvhead=2, head_dim=8, rope=1,
                                   rope_theta=1e6, q_block=8, gate=0,
                                   window=0),
        ("moe", 0): dict(nexpert=8, topk=3, nhidden=24, expert_block=4,
                         expert_first=2, expert_count=4,
                         score_func="softmax", nshared=0),
        ("moe", 1): dict(nexpert=8, topk=3, nhidden=24, expert_block=4,
                         expert_first=2, expert_count=4,
                         score_func="sigmoid", nshared=1, bias_sigma=0.5)}
    for (kind, which), c in cfgs.items():
        layer = create_layer(kind, [(k, str(v)) for k, v in c.items()])
        layer.infer_shape([seq_shape(T, D)])
        p = layer.init_params(jax.random.PRNGKey(0))
        st = layer.init_state()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, T, D))

        def f(p, x):
            return layer.forward(p, st, [x], True, None)[0][0]

        g = jax.grad(lambda p, x: jnp.sum(jnp.sin(f(p, x))),
                     argnums=(0, 1))(p, x)
        assert _digest([f(p, x)] + jax.tree_util.tree_leaves(g)) \
            == want[(kind, which)], (kind, which)


def _attention_layer(kind):
    rope = TINY["rope_parameters"][kind]
    cfg = dict(nhead=4, nkvhead=2, head_dim=8, rope=1, gate=0, eps=1e-6,
               q_block=8, rope_theta=rope["rope_theta"])
    if kind == "sliding_attention":
        cfg["window"] = TINY["sliding_window"]
    else:
        cfg.update(window=0, rope_type="yarn", rope_factor=rope["factor"],
                   original_max_position_embeddings=rope[
                       "original_max_position_embeddings"],
                   beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
                   attention_factor=rope["attention_factor"])
    layer = create_layer("gqa_attention",
                         [(k, str(v)) for k, v in cfg.items()])
    layer.infer_shape([seq_shape(T, D)])
    return layer, layer.init_params(jax.random.PRNGKey(4))


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_a_sliding_and_a_full_layer_match_the_reference(kind):
    """The layer's value and its gradients in every weight and the input,
    float32 at highest precision, against the reference's attention of
    one sequence at a time (full score rows, the band's mask, YaRN
    transcribed from the formula)."""
    layer, p = _attention_layer(kind)
    assert set(p) == {"wq", "wk", "wv", "wo", "qnorm", "knorm"}
    p = dict(p, qnorm=1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), (8,)), knorm=0.9 * jnp.ones((8,)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, D))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, D))

    def mine(p, x):
        return layer.forward(p, {}, [x], True, None)[0][0]

    def theirs(p, x):
        return jax.vmap(lambda one: ref.attention(
            p, one, TINY, kind, None, 8, False))(x)

    with jax.default_matmul_precision("highest"):
        y0, y1 = mine(p, x), theirs(p, x)
        g0 = jax.grad(lambda p, x: jnp.sum(w * mine(p, x)),
                      argnums=(0, 1))(p, x)
        g1 = jax.grad(lambda p, x: jnp.sum(w * theirs(p, x)),
                      argnums=(0, 1))(p, x)
    _close(y0, y1, 2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        _close(a, b, 5e-5)


def test_the_window_and_yarn_change_what_a_layer_computes():
    """Neither key is inert: the full layer's tables are YaRN's (scaled by
    attention_factor) and the sliding layer's query at 15 sees keys 10 to
    15 only."""
    full, p = _attention_layer("full_attention")
    assert full.yarn() is not None and full.window == 0
    sliding, _ = _attention_layer("sliding_attention")
    assert sliding.yarn() is None and sliding.window == 6
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, D))
    y_full = full.forward(p, {}, [x], True, None)[0][0]
    y_band = sliding.forward(p, {}, [x], True, None)[0][0]
    assert float(jnp.abs(y_full - y_band)[:, 6:].max()) > 1e-3
    # the first six positions see the same keys under both masks; only
    # the tables differ there
    plain = create_layer("gqa_attention", [
        ("nhead", "4"), ("nkvhead", "2"), ("head_dim", "8"), ("gate", "0"),
        ("q_block", "8"), ("rope_theta", "100"), ("window", "0")])
    plain.infer_shape([seq_shape(T, D)])
    y_plain = plain.forward(p, {}, [x], True, None)[0][0]
    assert float(jnp.abs(y_full - y_plain).max()) > 1e-3


def test_gqa_attention_refuses_an_unknown_rope_type_and_bad_yarn_keys():
    with pytest.raises(ValueError, match="rope_type"):
        create_layer("gqa_attention", [("rope_type", "ntk")])
    layer = create_layer("gqa_attention", [
        ("nhead", "4"), ("nkvhead", "2"), ("head_dim", "8"),
        ("rope_type", "yarn"), ("rope_factor", "4")])
    with pytest.raises(ValueError, match="yarn"):
        layer.infer_shape([seq_shape(T, D)])
