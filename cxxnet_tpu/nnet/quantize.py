"""Post-training low-precision inference: calibration, scales, dequant.

The reference never shipped a quantized path — its speed came from
codegen'd fused f32 kernels. On the MXU the remaining inference lever
is operand width: int8 contractions run at twice the bf16 MAC rate and
a quarter of the f32 HBM bytes (fp8 similarly where the backend
supports it). This module owns everything between a trained f32
snapshot and a servable quantized graph:

* **calibration** (:class:`Calibrator`) — stream an eval iterator
  through the frozen net and record per-channel activation amax at the
  input of every quantizable contraction (conv / fullc), plus
  per-out-channel weight amax over the *eval-folded* weights (the
  ``bn_fold_eval`` fold is part of the served graph, so ranges are
  taken over what serving will actually contract).
* **scales in the snapshot** — ranges ride as ``quant/<layer>/...``
  arrays inside the npz, so the PR 5 content digest covers them and
  ``ckpt_verify`` treats a quantized snapshot as a first-class
  verified artifact; the summary (dtype, batch count, fold state)
  rides in ``__meta__["quantized"]``.
* **activation** (:func:`attach`) — ``serve_dtype = int8|fp8|bf16``
  turns the recorded ranges into symmetric scales (per-tensor for
  activations, per-out-channel for weights) and pins a
  :class:`QuantSpec` on each quantizable layer object; the eval
  forward then quantizes operands on device, contracts in the low
  dtype (int32 / f32 accumulation), and folds the per-channel dequant
  into the conv epilogue (``layers/conv.py``).
  Training forwards never consult the spec.

One fallback is part of the contract: a backend that rejects native
int8/fp8 contractions still *computes the quantized numbers* (operands
round through the quantized grid but contract in f32 — bit-identical
values, no speedup), and the ``quantized_model`` record says so
(``native: false``). Parity against
the f32 eval output is gated by ``task = quantize``
(doc/perf_profile.md "Low-precision inference").
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# symmetric quantization grids: int8 keeps -128 out so +/-amax map to
# +/-127 with one scale; fp8 e4m3 saturates at its max finite 448
QMAX = {"int8": 127.0, "fp8": 448.0}

SERVE_DTYPES = ("float32", "bfloat16", "int8", "fp8")

QUANT_PREFIX = "quant/"

# graph layer types whose contraction quantizes (the torch oracle layer
# is a test fixture)
_QUANT_TYPES = {"conv": "conv", "fullc": "dot"}

# amax floor: a dead channel (all-zero weights/activations) must not
# produce a zero scale (dequant would divide by it)
_AMAX_FLOOR = 1e-8


def normalize_serve_dtype(val: str) -> str:
    """Canonical ``serve_dtype`` value (accepts the short aliases)."""
    alias = {"f32": "float32", "bf16": "bfloat16", "float8": "fp8",
             "float8_e4m3": "fp8"}
    v = alias.get(val, val)
    if v not in SERVE_DTYPES:
        raise ValueError("serve_dtype must be one of %s (got %r)"
                         % ("|".join(SERVE_DTYPES), val))
    return v


FP8 = jnp.float8_e4m3fn                  # the fp8 storage dtype


_NATIVE_CACHE: Dict[tuple, bool] = {}


def backend_native(dtype: str, op: str) -> bool:
    """True when the backend contracts ``dtype`` operands natively
    (``op`` = 'dot' | 'conv'). Probed once with a tiny op; a backend
    whose compiler or runtime REJECTS the op (a ``JaxRuntimeError``)
    falls back to the f32-simulated contraction — same values, no
    speedup — and the ``quantized_model`` record says ``native:
    false``. Any other failure of the probe is a bug and propagates."""
    key = (dtype, op, jax.default_backend())
    if key in _NATIVE_CACHE:
        return _NATIVE_CACHE[key]
    qt, acc = (jnp.int8, jnp.int32) if dtype == "int8" \
        else (FP8, jnp.float32)
    try:
        if op == "dot":
            a = jnp.ones((8, 8), qt)
            out = jnp.dot(a, a, preferred_element_type=acc)
        else:
            x = jnp.ones((1, 4, 4, 8), qt)
            w = jnp.ones((3, 3, 8, 8), qt)
            out = jax.lax.conv_general_dilated(
                x, w, window_strides=(1, 1), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=acc)
        jax.block_until_ready(out)       # one-time capability probe
        ok = True
    except jax.errors.JaxRuntimeError:
        ok = False                       # rejected: simulate in f32
    _NATIVE_CACHE[key] = ok
    return ok


class QuantSpec:
    """Per-layer runtime recipe, pinned on the layer object by
    :func:`attach`. ``dtype`` is the *effective* quantized dtype
    ('int8' | 'fp8' | 'bfloat16'); scales are symmetric — per-tensor
    for the activation, per-out-channel for the weight."""

    __slots__ = ("dtype", "x_scale", "w_scale", "native")

    def __init__(self, dtype: str, x_scale: float = 1.0,
                 w_scale=None, native: bool = False):
        self.dtype = dtype
        self.x_scale = x_scale
        self.w_scale = w_scale           # jnp (out,) vector, or None
        self.native = native

    @property
    def is_affine(self) -> bool:
        return self.dtype in ("int8", "fp8")

    def dequant_vec(self) -> jnp.ndarray:
        """Per-out-channel dequantization factors (f32): the epilogue
        multiplies the raw accumulator by ``x_scale * w_scale``."""
        return (self.w_scale * jnp.float32(self.x_scale)).astype(
            jnp.float32)

    def quantize_x(self, x: jnp.ndarray) -> jnp.ndarray:
        return quantize_tensor(x, jnp.float32(self.x_scale), self.dtype,
                               self.native)

    def quantize_w(self, w: jnp.ndarray) -> jnp.ndarray:
        return quantize_tensor(w, self.w_scale.astype(jnp.float32),
                               self.dtype, self.native)

    def acc_dtype(self):
        """preferred_element_type for the quantized contraction."""
        if self.native and self.dtype == "int8":
            return jnp.int32
        return jnp.float32


def quantize_tensor(v: jnp.ndarray, scale, dtype: str,
                    native: bool) -> jnp.ndarray:
    """Symmetric quantization onto the ``dtype`` grid. ``scale``
    broadcasts over the last (out-channel) axis for weights or is a
    scalar for activations. Non-native backends keep the values on the
    quantized grid but store them f32, so the simulated contraction
    computes the same numbers the native one would (int8 exactly; fp8
    modulo the accumulator — both inside the parity gate)."""
    qmax = QMAX[dtype]
    vf = v.astype(jnp.float32) / scale
    if dtype == "int8":
        q = jnp.clip(jnp.round(vf), -qmax, qmax)
        return q.astype(jnp.int8) if native else q
    q = jnp.clip(vf, -qmax, qmax)
    q = q.astype(FP8)                    # e4m3 mantissa rounding
    return q if native else q.astype(jnp.float32)


class QuantTarget(NamedTuple):
    li: int                              # layer (connection) index
    lkey: str                            # param layer key (table key)
    in_node: int                         # activation node calibrated
    kind: str                            # 'conv' | 'dot'


def quantizable(net) -> List[QuantTarget]:
    """The net's quantizable contractions: conv / fullc layers that own
    their params (shared layers and shared primaries are excluded —
    one shared weight serving two sites would need two activation
    scales) and carry no channel-alignment annotations (the padded
    physical layout and the per-channel scales would have to agree
    channel-for-channel; channel_pad is a training-bench knob, serving
    graphs run unpadded)."""
    g = net.graph
    shared_primaries = set(info.primary_layer_index
                           for info in g.layers if info.type == "share")
    out = []
    for li, info in enumerate(g.layers):
        kind = _QUANT_TYPES.get(info.type)
        if kind is None or li in shared_primaries:
            continue
        layer = net.layer_objs[li]
        if (getattr(layer, "_in_layout", None) is not None
                or getattr(layer, "_out_pad", 0)
                or getattr(layer, "_layout", None) is not None):
            continue
        out.append(QuantTarget(li, g.layer_key(li), info.nindex_in[0],
                               kind))
    return out


def folded_weight(trainer, li: int, lkey: str) -> np.ndarray:
    """Host copy of the weight exactly as the eval graph contracts it:
    under ``bn_fold_eval`` the BN partner's running-stats scale is
    folded in (conv.py applies ``w * _fold_scale``), so weight ranges
    are taken over the folded tensor."""
    net = trainer.net
    w = np.asarray(trainer.params[lkey]["wmat"], np.float32)
    if net._bn_fold_eval and li in net._fold_pairs:
        bn_li = net._fold_pairs[li]
        bn = net.layer_objs[bn_li]
        bkey = net.graph.layer_key(net.graph.param_layer_index(bn_li))
        bw = np.asarray(trainer.params[bkey]["wmat"], np.float32)
        bv = np.asarray(trainer.net_state[bkey]["running_var"],
                        np.float32)
        w = w * (bw / np.sqrt(bv + bn.eps))
    return w


class Calibrator:
    """Streams eval batches through the net, recording per-channel
    activation amax at every quantizable layer input. One jitted
    program computes ALL the amax vectors in a single forward per
    batch (registered in ``lint/config.py PROGRAM_BUILDERS``)."""

    def __init__(self, trainer):
        assert trainer._initialized, "calibrate after load_model"
        self.trainer = trainer
        self.targets = quantizable(trainer.net)
        self._amax: Dict[str, np.ndarray] = {}
        self._prog = None
        self.batches = 0

    def _build_amax_program(self):
        net = self.trainer.net
        nodes = tuple(t.in_node for t in self.targets)

        def amax_step(params, net_state, data, mask):
            vals, _, _ = net.forward(params, net_state, data,
                                     is_train=False, mask=mask)
            out = []
            for ni in nodes:
                v = net.depad_node(ni, vals[ni]).astype(jnp.float32)
                axes = tuple(range(v.ndim - 1))
                out.append(jnp.max(jnp.abs(v), axis=axes))
            return out
        return jax.jit(amax_step)

    def observe(self, batch) -> None:
        """Fold one batch's activation ranges in. Padded tail rows are
        zeros — they can never raise an amax, so no mask gymnastics."""
        t = self.trainer
        if self._prog is None:
            self._prog = self._build_amax_program()
        vecs = self._prog(t.params, t.net_state,
                          t._put_batch_array(batch.data),
                          t._put_mask(batch))
        for tgt, v in zip(self.targets, vecs):
            a = np.asarray(v)            # tiny per-channel D2H, offline
            cur = self._amax.get(tgt.lkey)
            self._amax[tgt.lkey] = a if cur is None \
                else np.maximum(cur, a)
        self.batches += 1

    def finish(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Range tables: per-channel activation amax + per-out-channel
        amax of the eval-folded weights. Scales derive at attach time
        (one calibration serves both int8 and fp8)."""
        assert self.batches > 0, "calibrate on at least one batch"
        tables: Dict[str, Dict[str, np.ndarray]] = {}
        for tgt in self.targets:
            w = folded_weight(self.trainer, tgt.li, tgt.lkey)
            w_amax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
            tables[tgt.lkey] = {
                "x_amax": self._amax[tgt.lkey].astype(np.float32),
                "w_amax": w_amax.astype(np.float32),
            }
        return tables


def tables_from_blob(blob) -> Dict[str, Dict[str, np.ndarray]]:
    """Collect ``quant/<layer>/<field>`` arrays from a snapshot blob
    (they are digest-covered like every other array)."""
    tables: Dict[str, Dict[str, np.ndarray]] = {}
    for k in blob:
        if not k.startswith(QUANT_PREFIX):
            continue
        lkey, field = k[len(QUANT_PREFIX):].rsplit("/", 1)
        tables.setdefault(lkey, {})[field] = np.asarray(blob[k])
    return tables


def attach(trainer) -> Dict[str, Any]:
    """Activate the trainer's ``serve_dtype`` on its layer objects.

    Returns the report behind the ``quantized_model`` telemetry record:
    effective dtype, quantized layer count, fallback count (targets
    without a table entry), and whether the backend contracts natively.
    float32 clears every spec; bfloat16 needs no tables; int8/fp8
    require a calibrated snapshot and raise without one.
    """
    net = trainer.net
    for layer in net.layer_objs:
        layer._quant = None
    dtype = trainer.serve_dtype
    if dtype == "float32":
        return {"active": False}
    targets = quantizable(net)
    report = {"active": True, "dtype": dtype, "layers": 0,
              "fallback_layers": 0, "native": False}
    if dtype == "bfloat16":
        for tgt in targets:
            net.layer_objs[tgt.li]._quant = QuantSpec("bfloat16")
            report["layers"] += 1
        report["native"] = True
        return report
    tables = trainer.quant_tables
    if not tables:
        raise ValueError(
            "serve_dtype=%s needs a calibrated snapshot: run "
            "task=quantize over this model first (doc/perf_profile.md "
            "\"Low-precision inference\")" % dtype)
    qmax = QMAX[dtype]
    meta_fold = trainer.quant_meta.get("bn_fold_eval")
    if meta_fold is not None and bool(meta_fold) != net._bn_fold_eval:
        from ..monitor import warn_once
        warn_once("quant_fold_mismatch",
                  "snapshot was calibrated with bn_fold_eval=%s but "
                  "this config runs bn_fold_eval=%s; weight scales "
                  "were taken over the other graph"
                  % (meta_fold, net._bn_fold_eval))
    natives = []
    for tgt in targets:
        tab = tables.get(tgt.lkey)
        if tab is None or "x_amax" not in tab or "w_amax" not in tab:
            report["fallback_layers"] += 1
            continue
        x_scale = float(max(float(np.max(tab["x_amax"])),
                            _AMAX_FLOOR) / qmax)
        w_scale = np.maximum(tab["w_amax"].astype(np.float32),
                             _AMAX_FLOOR) / qmax
        native = backend_native(dtype, tgt.kind)
        if (tgt.kind == "conv"
                and net.layer_objs[tgt.li].param.num_group > 1):
            # the capability probe runs ungrouped; grouped low-dtype
            # conv support varies by backend — simulate (same values)
            native = False
        natives.append(native)
        net.layer_objs[tgt.li]._quant = QuantSpec(
            dtype, x_scale=x_scale, w_scale=jnp.asarray(w_scale),
            native=native)
        report["layers"] += 1
    report["native"] = bool(natives) and all(natives)
    return report
