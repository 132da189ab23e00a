"""Spatial layers: convolution, pooling, LRN, batch norm.

TPU-native design notes:

- conv lowers to ``lax.conv_general_dilated`` in NHWC/HWIO — XLA tiles it
  straight onto the MXU; the reference's im2col + chunked GEMM
  (convolution_layer-inl.hpp:79-154, temp_col_max budget) is a GPU-memory
  workaround that XLA makes unnecessary.
- pooling lowers to ``lax.reduce_window``; the reference's ceil-mode
  output formula and border-truncation semantics
  (pooling_layer-inl.hpp:119-123) are reproduced exactly by padding the
  base pad with zeros (mshadow ``pad()`` is a zero pad) and the ceil
  overhang with the reducer's identity.
- batch norm follows the reference's batch-statistics and
  running-average semantics (batch_norm_layer-inl.hpp:120-175) with one
  deliberate improvement: moments are taken over the GLOBAL batch.
  Under data parallelism GSPMD all-reduces the per-shard sums (sync BN)
  so a dp run computes exactly what the same global batch computes on
  one device — unlike the reference, where each device normalized by
  its private sub-batch and dp subtly changed training (SURVEY.md §7
  hard part 6).  Padded tail rows (num_batch_padd) are excluded from
  the moments via the batch mask.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
import numpy as np

from .base import Layer, LayerParam, Shape3


def _conv_out_dim(size: int, pad: int, k: int, stride: int) -> int:
    # convolution_layer-inl.hpp:178-181 (floor mode)
    return (size + 2 * pad - k) // stride + 1


def _pool_out_dim(size: int, pad: int, k: int, stride: int) -> int:
    # pooling_layer-inl.hpp:119-123 (ceil mode, window start clamped)
    return min(size + 2 * pad - k + stride - 1, size + 2 * pad - 1) // stride + 1


def _max_pool(x, kh, kw, stride, padding="VALID"):
    """Max pooling via reduce_window; backward is XLA's
    select-and-scatter. Two hand-written VJPs were tried and measured
    SLOWER end-to-end on this hardware, so autodiff stays in charge:
    round 2, an offset-loop interior-padded scatter for strided pools
    (2.2x slower on AlexNet); round 3, an equality-based kh*kw
    shifted compare-add backward for stride-1 pools (kaiming 8,546 ->
    7,906 img/s, Inception-BN flat) — the dense stride-1
    select-and-scatter looked expensive in isolation (2.7 ms/step on
    kaiming's 109x109 stem pool) but XLA overlaps it better than the
    fused-loop alternative."""
    return jax.lax.reduce_window(
        x, -jnp.inf if x.dtype == jnp.float32 else x.dtype.type(-jnp.inf),
        jax.lax.max,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, stride, stride, 1),
        padding=padding)


class ConvolutionLayer(Layer):
    """Grouped 2-D convolution; weights HWIO (kh, kw, in_ch/group, out_ch)."""

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        p = self.param
        if p.num_channel <= 0:
            raise ValueError("conv: must set nchannel correctly")
        if p.kernel_height <= 0 or p.kernel_width <= 0:
            raise ValueError("conv: must set kernel_size correctly")
        if s.ch % p.num_group != 0 or p.num_channel % p.num_group != 0:
            raise ValueError("conv: channels must divide group size")
        if p.kernel_width > s.x or p.kernel_height > s.y:
            raise ValueError("conv: kernel size exceeds input")
        if p.num_input_channel == 0:
            p.num_input_channel = s.ch
        elif p.num_input_channel != s.ch:
            raise ValueError("conv: input channel count not consistent")
        oy = _conv_out_dim(s.y, p.pad_y, p.kernel_height, p.stride)
        ox = _conv_out_dim(s.x, p.pad_x, p.kernel_width, p.stride)
        self.in_shapes = [s]
        self.out_shapes = [Shape3(p.num_channel, oy, ox)]
        return self.out_shapes

    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        p = self.param
        in_pg = p.num_input_channel // p.num_group
        shape = (p.kernel_height, p.kernel_width, in_pg, p.num_channel)
        # fan convention follows the reference's GEMM view: wmat is
        # (nch/group, in_pg*kh*kw) per group, fan = (in, out) per filter
        fan_in = in_pg * p.kernel_height * p.kernel_width
        fan_out = p.num_channel // p.num_group
        wmat = p.rand_init_weight(key, shape, fan_in, fan_out)
        out = {"wmat": wmat}
        if p.no_bias == 0:
            out["bias"] = jnp.full((p.num_channel,), p.init_bias, jnp.float32)
        return out

    def _space_to_depth_conv(self, x, w):
        """Strided entry conv as a dense conv over depth blocks.

        A stride-s conv with few input channels (AlexNet conv1: 11x11
        s4 over RGB) wastes the MXU — 3 of 128 input lanes are live.
        Rearranging s x s input blocks into depth (228^2 x 3 ->
        57^2 x 48) and folding the kernel the same way yields an
        equivalent stride-1 conv with ceil(k/s)^2 taps over s^2*C
        channels, which XLA tiles efficiently. Numerically identical
        modulo summation order.
        """
        p = self.param
        s = p.stride
        # channel counts come from the operands (physical under the
        # channel_pad pass), not the logical layer params
        k, c, o = p.kernel_height, x.shape[-1], w.shape[-1]
        kp = -(-k // s) * s                   # kernel padded to mult of s
        b, h, wd = x.shape[0], x.shape[1], x.shape[2]
        oy = (h - k) // s + 1
        ox = (wd - k) // s + 1
        h2 = (oy - 1) * s + kp
        w2 = (ox - 1) * s + kp
        # floor-mode output can leave uncovered tail rows (h2 < h when
        # the kernel is a stride multiple): crop them, then zero-pad up
        # to the block-aligned extent
        if h2 < h or w2 < wd:
            x = x[:, :min(h2, h), :min(w2, wd), :]
        x = jnp.pad(x, ((0, 0), (0, h2 - x.shape[1]),
                        (0, w2 - x.shape[2]), (0, 0)))
        # NHWC space-to-depth(s)
        x = x.reshape(b, h2 // s, s, w2 // s, s, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
            b, h2 // s, w2 // s, s * s * c)
        # HWIO kernel: pad to (kp, kp), fold s x s taps into depth
        w4 = jnp.pad(w, ((0, kp - k), (0, kp - k), (0, 0), (0, 0)))
        w4 = w4.reshape(kp // s, s, kp // s, s, c, o)
        w4 = w4.transpose(0, 2, 1, 3, 4, 5).reshape(
            kp // s, kp // s, s * s * c, o)
        return jax.lax.conv_general_dilated(
            x, w4, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def forward(self, params, state, inputs, is_train, rng):
        p = self.param
        x = inputs[0]
        w = params["wmat"]
        # serve_dtype quantization spec (nnet/quantize.attach); only
        # the eval/pred forward ever consults it
        q = None if is_train else getattr(self, "_quant", None)
        quant = q is not None and q.is_affine
        if not is_train:
            # device-resident serve weights (trainer.freeze_serve_
            # weights): the fold/quantize/cast already happened ONCE at
            # freeze, so ``w`` arrives pre-transformed and the ``_r_*``
            # epilogue vectors ride the tree as arguments. Key presence
            # is static (pytree structure), so this branch costs
            # nothing when the tree is the raw master tree.
            out = self._forward_resident(params, state, x, w, q)
            if out is not None:
                return out
        # BN epilogue folded into the conv (eval/pred path): the net's
        # bn_fold_eval pass injects the per-out-channel _fold_scale /
        # _fold_shift (from the BN's running stats) and the downstream
        # BN runs as identity — w*scale folds into the (small) weight
        # tensor, deleting the per-layer elementwise pass entirely.
        fold_scale = params.get("_fold_scale")
        out_pad = getattr(self, "_out_pad", 0)
        if fold_scale is not None:
            w = w * fold_scale          # f32, per out channel (HWIO)
        # channel-alignment annotations (nnet/layout.py): zero weight
        # rows absorb a padded input's dead channels, zero weight
        # columns emit an aligned (padded) output — both provably-zero
        # extensions of the same contraction, bit-identical math
        in_layout = getattr(self, "_in_layout", None)
        if in_layout is not None:
            parts, off = [], 0
            for valid, padc in in_layout:
                parts.append(w[:, :, off:off + valid, :])
                if padc:
                    parts.append(jnp.zeros(
                        w.shape[:2] + (padc, w.shape[3]), w.dtype))
                off += valid
            w = jnp.concatenate(parts, axis=2)
        if out_pad:
            w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, out_pad)))
        bf16 = (p.compute_dtype == "bfloat16"
                or (q is not None and q.dtype == "bfloat16"))
        if quant:
            # int8/fp8 contraction: symmetric per-tensor activation /
            # per-out-channel weight quantization on device, the MXU
            # contracts the low dtype (int32 or f32 accumulation), and
            # the per-channel dequant folds into the epilogue below —
            # channel-alignment layouts never reach here (quantize
            # .quantizable excludes annotated layers)
            y = jax.lax.conv_general_dilated(
                q.quantize_x(x), q.quantize_w(w),
                window_strides=(p.stride, p.stride),
                padding=[(p.pad_y, p.pad_y), (p.pad_x, p.pad_x)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=p.num_group,
                preferred_element_type=q.acc_dtype())
        else:
            if bf16:
                # both operands bf16, output bf16 (the conv VJP requires
                # matching operand/cotangent dtypes; MXU still
                # accumulates in f32 internally)
                x = x.astype(jnp.bfloat16)
                w = w.astype(jnp.bfloat16)
            y = self._float_conv(x, w, bf16)
        # bf16 outputs stay bf16: activations ride low-precision through
        # relu/pool/lrn to the loss (which upcasts) — per-layer
        # f32 round-trips were a wall of convert fusions in the profile
        if fold_scale is not None:
            b = params["_fold_shift"]
            if p.no_bias == 0:
                b = b + params["bias"] * fold_scale
        elif p.no_bias == 0:
            b = params["bias"]
        else:
            b = None
        relu = fold_scale is not None and "_fold_relu" in params
        if quant:
            # the quantized dequant as one per-channel scale+shift
            # (+relu) pass that emits the compute dtype
            dq = q.dequant_vec()
            shift = b if b is not None else jnp.zeros_like(dq)
            out_dtype = jnp.bfloat16 if bf16 else jnp.float32
            yf = y.astype(jnp.float32) * dq + shift
            if relu:
                yf = jax.nn.relu(yf)
            y = yf.astype(out_dtype)
        else:
            if b is not None:
                if out_pad:               # padded channels stay zero
                    b = jnp.pad(b, ((0, out_pad),))
                y = y + b.astype(y.dtype)
            if relu:
                y = jax.nn.relu(y)
        # named for the remat=conv policy (trainer._wrap_loss_fn): under
        # save_only_these_names("conv_out") the backward keeps conv
        # outputs and recomputes BN/activation/pool between them;
        # identity when no checkpoint policy is active
        y = checkpoint_name(y, "conv_out")
        return [y], state

    def _forward_resident(self, params, state, x, w, q):
        """Eval forward over a frozen serve weight tree, or None when
        ``params`` carries no residency markers (legacy path). The
        arithmetic mirrors the in-graph fold/quantize path op for op —
        the tree just holds the weight-side results precomputed — so
        outputs are bit-identical to the legacy trace."""
        p = self.param
        relu = False
        shift = params.get("_r_shift")
        if shift is None:
            shift = params.get("_r_shift_relu")
            relu = shift is not None
        if shift is None:
            return None
        dq = params.get("_r_dequant")
        if dq is not None:
            # w is pre-quantized (and pre-folded); only the batch-sized
            # activation quantizes per dispatch
            y = jax.lax.conv_general_dilated(
                q.quantize_x(x), w,
                window_strides=(p.stride, p.stride),
                padding=[(p.pad_y, p.pad_y), (p.pad_x, p.pad_x)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=p.num_group,
                preferred_element_type=q.acc_dtype())
            bf16 = (p.compute_dtype == "bfloat16"
                    or q.dtype == "bfloat16")
            out_dtype = jnp.bfloat16 if bf16 else jnp.float32
            yf = y.astype(jnp.float32) * dq + shift
            if relu:
                yf = jax.nn.relu(yf)
            y = yf.astype(out_dtype)
        else:
            # pre-folded (and possibly pre-cast) float weights
            bf16 = (p.compute_dtype == "bfloat16"
                    or (q is not None and q.dtype == "bfloat16"))
            if bf16:
                x = x.astype(jnp.bfloat16)
                w = w.astype(jnp.bfloat16)   # no-op: tree holds bf16
            y = self._float_conv(x, w, bf16)
            y = y + shift.astype(y.dtype)
            if relu:
                y = jax.nn.relu(y)
        y = checkpoint_name(y, "conv_out")
        return [y], state

    def _float_conv(self, x, w, bf16):
        """The two float conv lowerings (space-to-depth entry rewrite,
        general NHWC/HWIO conv)."""
        p = self.param
        if (p.stride > 1 and p.num_group == 1 and x.shape[-1] <= 8
                and p.kernel_height == p.kernel_width):
            # padded entry convs (Inception stem 7x7 s2 p3) zero-pad
            # explicitly, then the same VALID space-to-depth rewrite
            # applies; the pad is tiny at <=8 input channels
            if p.pad_y or p.pad_x:
                x = jnp.pad(x, ((0, 0), (p.pad_y, p.pad_y),
                                (p.pad_x, p.pad_x), (0, 0)))
            y = self._space_to_depth_conv(x, w)
        else:
            y = jax.lax.conv_general_dilated(
                x, w,
                window_strides=(p.stride, p.stride),
                padding=[(p.pad_y, p.pad_y), (p.pad_x, p.pad_x)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=p.num_group,
                preferred_element_type=None if bf16 else jnp.float32)
        return y


class PoolingLayer(Layer):
    """max / sum / avg pooling with reference ceil-mode shape semantics.

    mode: 'max' | 'sum' | 'avg'. pre_relu fuses a relu before pooling
    (the reference's relu_max_pooling, layer_impl-inl.hpp:55-56).
    """

    def __init__(self, mode: str, cfg=(), pre_relu: bool = False):
        self.mode = mode
        self.pre_relu = pre_relu
        super().__init__(cfg)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        p = self.param
        if p.kernel_height <= 0 or p.kernel_width <= 0:
            raise ValueError("pooling: must set kernel_size correctly")
        if p.kernel_width > s.x or p.kernel_height > s.y:
            raise ValueError("pooling: kernel size exceeds input")
        oy = _pool_out_dim(s.y, p.pad_y, p.kernel_height, p.stride)
        ox = _pool_out_dim(s.x, p.pad_x, p.kernel_width, p.stride)
        self.in_shapes = [s]
        self.out_shapes = [Shape3(s.ch, oy, ox)]
        return self.out_shapes

    def _pool(self, x: jnp.ndarray) -> jnp.ndarray:
        p = self.param
        oy, ox = self.out_shapes[0].y, self.out_shapes[0].x
        # base pad is a zero pad (mshadow pad()); the ceil overhang is
        # truncated-window semantics -> pad with the reducer's identity.
        # Padding with the identity folds into reduce_window's native
        # padding (no materialized pad op); for max the zero base pad
        # differs from the -inf identity, so it stays an explicit pad.
        py, px = p.pad_y, p.pad_x
        if self.mode == "max" and (py or px):
            x = jnp.pad(x, ((0, 0), (py, py), (px, px), (0, 0)))
            py = px = 0
        need_y = (oy - 1) * p.stride + p.kernel_height
        need_x = (ox - 1) * p.stride + p.kernel_width
        ey = max(0, need_y - (x.shape[1] + 2 * py))
        ex = max(0, need_x - (x.shape[2] + 2 * px))
        padding = ((0, 0), (py, py + ey), (px, px + ex), (0, 0))
        if self.mode == "max":
            y = _max_pool(x, p.kernel_height, p.kernel_width, p.stride,
                          padding)
        else:
            y = jax.lax.reduce_window(
                x, x.dtype.type(0), jax.lax.add,
                window_dimensions=(1, p.kernel_height, p.kernel_width, 1),
                window_strides=(1, p.stride, p.stride, 1),
                padding=padding)
            if self.mode == "avg":
                y = y * (1.0 / (p.kernel_height * p.kernel_width))
        return y

    def forward(self, params, state, inputs, is_train, rng):
        x = inputs[0]
        if self.pre_relu:
            x = jax.nn.relu(x)
        return [self._pool(x)], state


class InsanityPoolingLayer(PoolingLayer):
    """Stochastic-displacement max pooling (insanity_pooling_layer-inl.hpp).

    During training each input pixel is displaced by one step in a random
    direction with probability (1-keep), then ceil-mode pooling runs over
    the displaced image; inference is plain pooling. The reference
    implements this as a hand-written CUDA expression Plan — here the
    displacement is a vectorized 5-way select, and XLA fuses it into the
    reduce_window.
    """

    def __init__(self, mode: str, cfg=()):
        self.p_keep = 1.0
        super().__init__(mode, cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "keep":
            self.p_keep = float(val)

    def forward(self, params, state, inputs, is_train, rng):
        x = inputs[0]
        if not is_train:
            return [self._pool(x)], state
        if self.param.pad_y or self.param.pad_x:
            raise ValueError("insanity pooling: pad unsupported in training "
                             "(matches reference behavior)")
        assert rng is not None
        flag = jax.random.uniform(rng, x.shape)
        delta = (1.0 - self.p_keep) / 4.0
        # shifted copies with edge clamping (insanity_pooling:70-86)
        up = jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)      # loc_y-1
        down = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)    # loc_y+1
        left = jnp.concatenate([x[:, :, :1], x[:, :, :-1]], axis=2)
        right = jnp.concatenate([x[:, :, 1:], x[:, :, -1:]], axis=2)
        k = self.p_keep
        displaced = jnp.where(
            flag < k, x,
            jnp.where(flag < k + delta, up,
                      jnp.where(flag < k + 2 * delta, down,
                                jnp.where(flag < k + 3 * delta, left,
                                          right))))
        return [self._pool(displaced)], state


class LRNLayer(Layer):
    """Local response normalization across channels (lrn_layer-inl.hpp):
    out = x * (knorm + alpha/nsize * chpool_sum(x^2, nsize))^-beta."""

    def __init__(self, cfg=()):
        self.nsize = 3
        self.alpha = 0.001
        self.beta = 0.75
        self.knorm = 1.0
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "local_size":
            self.nsize = int(val)
        if name == "alpha":
            self.alpha = float(val)
        if name == "beta":
            self.beta = float(val)
        if name == "knorm":
            self.knorm = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        x = inputs[0]
        sq = x * x
        h = self.nsize // 2
        # mshadow chpool window is [c-h, c+h] inclusive, clipped — a
        # size-(2h+1) window sum over the channel (last NHWC) axis.
        # Summing 2h+1 shifted slices lets XLA fuse the whole normalizer
        # into elementwise ops with an equally cheap VJP; reduce_window's
        # select-scatter backward was ~16% of the AlexNet step time.
        win = 2 * h + 1
        if self.param.compute_dtype == "bfloat16":
            sq = sq.astype(jnp.bfloat16)
        pad = jnp.pad(sq, ((0, 0),) * (x.ndim - 1) + ((h, h),))
        c = x.shape[-1]
        norm = pad[..., 0:c]
        for i in range(1, win):
            norm = norm + pad[..., i:i + c]
        norm = norm.astype(jnp.float32) * (self.alpha / self.nsize) \
            + self.knorm
        if self.beta == 0.75:
            # norm^-0.75 = rsqrt(norm) * rsqrt(sqrt(norm)): two fast VPU
            # rsqrts instead of a transcendental pow
            r = jax.lax.rsqrt(norm)
            scale = r * jax.lax.rsqrt(jnp.sqrt(norm))
        else:
            scale = jnp.power(norm, -self.beta)
        return [x * scale.astype(x.dtype)], state


class BatchNormLayer(Layer):
    """Batch normalization, both reference variants.

    moving_avg=True  -> 'batch_norm'    (inference uses running stats)
    moving_avg=False -> 'batch_norm_no_ma' (inference recomputes batch
    stats — the reference's quirky but intentional behavior,
    batch_norm_layer-inl.hpp:147-173).

    Normalization axis follows the reference's fc/conv detection: conv
    nodes normalize per channel over (batch, y, x); matrix nodes per
    feature over batch. eps default 1e-10, running-average momentum 0.9.

    Moments are over the global batch — sync BN under data parallelism
    (a deliberate improvement over the reference's per-device stats; see
    module docstring) — and exclude padded tail rows via the mask.
    """

    needs_mask = True

    def __init__(self, moving_avg: bool, cfg=()):
        self.moving_avg = moving_avg
        self.init_slope = 1.0
        self.init_bias = 0.0
        self.eps = 1e-10
        self.bn_momentum = 0.9
        self.channel = 0
        # set by the net-level bn_fuse_relu pass (nnet/net.py): the
        # relu consuming this BN's output runs inside this layer and
        # the relu connection becomes identity — same math, one pass
        self.fuse_relu = False
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "init_slope":
            self.init_slope = float(val)
        if name == "init_bias":
            self.init_bias = float(val)
        if name == "eps":
            self.eps = float(val)
        if name == "bn_momentum":
            self.bn_momentum = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.channel = s.x if s.is_mat else s.ch
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        return {
            "wmat": jnp.full((self.channel,), self.init_slope, jnp.float32),
            "bias": jnp.full((self.channel,), self.init_bias, jnp.float32),
        }

    def init_state(self) -> Dict[str, jnp.ndarray]:
        if not self.moving_avg:
            return {}
        # reference initializes running stats to zero (bn:76-79)
        return {
            "running_exp": jnp.zeros((self.channel,), jnp.float32),
            "running_var": jnp.zeros((self.channel,), jnp.float32),
        }

    def _moments(self, x: jnp.ndarray, mask: Optional[jnp.ndarray]):
        """Single-pass masked moments: E[x²]-E[x]² with f32 accumulation.

        One fused read of the activation instead of two serialized
        passes (mean, then centered var): the sums s1/s2 share one
        fusion and the bf16->f32 convert folds into the reduction
        instead of materializing an upcast copy — BN stats were ~15% of
        the Inception-BN step before this. f32 accumulators keep the
        cancellation error negligible at these (2015-era) tensor sizes;
        var is clamped at 0 against rounding.
        """
        xf = x.astype(jnp.float32)          # fuses into the reduces
        axes = tuple(range(x.ndim - 1))     # all but channel/feature
        if mask is None:
            n = float(x.size // x.shape[-1])
            s1 = jnp.sum(xf, axis=axes)
            s2 = jnp.sum(xf * xf, axis=axes)
        else:
            # weight rows by the padded-tail mask:
            # (batch,) -> (batch,1[,1,1])
            w = mask.reshape((-1,) + (1,) * (x.ndim - 1))
            n = jnp.sum(mask) * (x.size // (x.shape[0] * x.shape[-1]))
            n = jnp.maximum(n, 1.0)
            s1 = jnp.sum(xf * w, axis=axes)
            s2 = jnp.sum(xf * xf * w, axis=axes)
        mean = s1 / n
        var = jnp.maximum(s2 / n - mean * mean, 0.0)
        return mean, var

    def forward(self, params, state, inputs, is_train, rng, mask=None):
        x = inputs[0]
        slope, bias = params["wmat"], params["bias"]
        # channel-alignment (nnet/layout.py): slope/bias scatter into
        # the physical channel positions with ZEROS in the pad gaps, so
        # padded channels come out exactly 0 (0*x + 0) and their
        # cotangents vanish; running stats stay logical in state
        layout = getattr(self, "_layout", None)
        if layout is not None:
            from ..nnet.layout import pad_channel_vec, take_valid
            slope = pad_channel_vec(slope, layout)
            bias = pad_channel_vec(bias, layout)
        if is_train:
            mean, var = self._moments(x, mask)
            if self.param.bn_fold_affine:
                # fold normalize+affine into per-channel scale/shift:
                # scale/shift are computed in f32 but APPLIED in the
                # compute dtype, so under bfloat16 the full-tensor
                # multiply-add runs in bf16 — unlike the unfused branch
                # and the eval path below, whose f32 scale broadcast
                # promotes the arithmetic to f32. The ~3-bit mantissa
                # loss is per-element rounding on an O(1)-magnitude
                # normalized tensor (bf16 BN agreement + gate coverage:
                # test_layers.py::test_batch_norm_fold_bf16,
                # test_inception_gate.py)
                scale = slope * jax.lax.rsqrt(var + self.eps)
                shift = bias - mean * scale
                out = x * scale.astype(x.dtype) + shift.astype(x.dtype)
            else:
                xhat = (x - mean) * jax.lax.rsqrt(var + self.eps)
                out = (xhat * slope + bias).astype(x.dtype)
            if self.fuse_relu:
                out = jax.nn.relu(out)
            if self.moving_avg:
                m = self.bn_momentum
                if layout is not None:    # state stays logical
                    mean, var = take_valid(mean, layout), \
                        take_valid(var, layout)
                state = dict(
                    state,
                    running_exp=state["running_exp"] * m + mean * (1 - m),
                    running_var=state["running_var"] * m + var * (1 - m))
            return [out], state
        if self.moving_avg:
            mean, var = state["running_exp"], state["running_var"]
            if layout is not None:        # scatter to physical (pads 0)
                mean = pad_channel_vec(mean, layout)
                var = pad_channel_vec(var, layout)
        else:
            mean, var = self._moments(x, mask)
        scale = slope * jax.lax.rsqrt(var + self.eps)
        out = (x * scale + (bias - mean * scale)).astype(x.dtype)
        if self.fuse_relu:
            out = jax.nn.relu(out)
        return [out], state
