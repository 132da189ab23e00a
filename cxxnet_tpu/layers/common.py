"""Dense / elementwise / structural layers.

TPU-native equivalents of the reference layer zoo (behavior parity with
the cited files; architecture is functional JAX, not a port):

- fullc        — fullc_layer-inl.hpp:14-146
- flatten      — flatten_layer-inl.hpp:11-44
- bias         — bias_layer-inl.hpp:14-120 (self-loop)
- relu/sigmoid/tanh/softplus — activation_layer-inl.hpp:12-41, op.h:15-101
- xelu         — xelu_layer-inl.hpp:15-51   (a>0 ? a : a/b)
- insanity (rrelu) — insanity_layer-inl.hpp:14-102 (random slope + anneal)
- prelu        — prelu_layer-inl.hpp:9-173 (custom vjp to match the
                 reference's slope gradient, which ignores clamp+noise)
- dropout      — dropout_layer-inl.hpp:12-66 (self-loop, inverted)
- concat/ch_concat — concat_layer-inl.hpp:12-79
- split        — split_layer-inl.hpp:12-45
- fixconn      — fixconn_layer-inl.hpp:14-93 (fixed sparse weights)
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import Layer, LayerParam, Shape3, as_mat, seq_shape
from ..utils.stream import open_stream


class FullConnectLayer(Layer):
    """y = x @ W + b.

    Weights are stored (in_features, num_hidden) — the natural layout for
    ``jnp.dot`` on the MXU. The reference stores the transpose
    (num_hidden, in) (fullc_layer-inl.hpp:37); the weight get/set API
    (trainer.get_weight) transposes to reference convention at the edge.
    """

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        if not (s.is_mat or s.is_seq):
            raise ValueError("fullc: input must be a matrix (flatten first)")
        if self.param.num_hidden <= 0:
            raise ValueError("fullc: must set nhidden correctly")
        if self.param.num_input_node == 0:
            self.param.num_input_node = s.x
        elif self.param.num_input_node != s.x:
            raise ValueError("fullc: input hidden nodes not consistent")
        self.in_shapes = [s]
        # a sequence node is projected position by position
        self.out_shapes = [seq_shape(s.y, self.param.num_hidden) if s.is_seq
                           else Shape3(1, 1, self.param.num_hidden)]
        return self.out_shapes

    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        p = self.param
        k1, _ = jax.random.split(key)
        # reference inits (num_hidden, num_input) with fan (in, out) —
        # same fan sum, so xavier bounds agree.
        wmat = p.rand_init_weight(k1, (p.num_input_node, p.num_hidden),
                                  p.num_input_node, p.num_hidden)
        out = {"wmat": wmat}
        if p.no_bias == 0:
            out["bias"] = jnp.full((p.num_hidden,), p.init_bias, jnp.float32)
        return out

    def forward(self, params, state, inputs, is_train, rng):
        x = inputs[0]
        w = params["wmat"]
        # serve_dtype quantization spec (nnet/quantize.attach): eval
        # forwards only — the int8/fp8 matmul contracts the quantized
        # operands and the per-out-channel dequant rides the epilogue
        q = None if is_train else getattr(self, "_quant", None)
        if q is not None and q.is_affine:
            # device-resident serve weights: ``_r_dequant`` in the tree
            # means the weight arrived pre-quantized at freeze — the
            # per-dispatch weight round/clip/cast disappears and the
            # dequant vector rides as an argument instead of a closure
            # constant baked into every bucket executable
            dq = params.get("_r_dequant")
            if dq is not None:
                y = jnp.dot(q.quantize_x(x), w,
                            preferred_element_type=q.acc_dtype())
                y = y.astype(jnp.float32) * dq
            else:
                y = jnp.dot(q.quantize_x(x), q.quantize_w(w),
                            preferred_element_type=q.acc_dtype())
                y = y.astype(jnp.float32) * q.dequant_vec()
            if self.param.no_bias == 0:
                y = y + params["bias"]
            return [y], state
        bf16 = (self.param.compute_dtype == "bfloat16"
                or (q is not None and q.dtype == "bfloat16"))
        if bf16:
            x = x.astype(jnp.bfloat16)
            w = w.astype(jnp.bfloat16)
        y = jnp.dot(x, w,
                    preferred_element_type=None if bf16 else jnp.float32)
        if self.param.no_bias == 0:
            y = y + params["bias"].astype(y.dtype)
        return [y], state


class FlattenLayer(Layer):
    """Reshape (b,y,x,ch) -> (b, ch*y*x) in reference NCHW c-order."""

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.in_shapes = [s]
        self.out_shapes = [Shape3(1, 1, s.flat_size)]
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        return [as_mat(inputs[0])], state


class BiasLayer(Layer):
    """Self-loop learned bias add on a matrix node."""

    self_loop = True

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        if not s.is_mat:
            raise ValueError("bias: only works on flattened nodes")
        if self.param.num_input_node == 0:
            self.param.num_input_node = s.x
        elif self.param.num_input_node != s.x:
            raise ValueError("bias: input hidden nodes not consistent")
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        return {"bias": jnp.full((self.param.num_input_node,),
                                 self.param.init_bias, jnp.float32)}

    def forward(self, params, state, inputs, is_train, rng):
        return [inputs[0] + params["bias"]], state


class ActivationLayer(Layer):
    """Elementwise activation; gradient follows from autodiff, which
    matches the reference's output-based grads (op.h:15-101)."""

    _FNS = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softplus": jax.nn.softplus,
    }

    def __init__(self, kind: str, cfg=()):
        self.kind = kind
        super().__init__(cfg)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        return [self._FNS[self.kind](inputs[0])], state


def _xelu(x: jnp.ndarray, b) -> jnp.ndarray:
    # op.h:51-55 — a>0 ? a : a/b  (division, not multiplication)
    return jnp.where(x > 0, x, x / b)


class XeluLayer(Layer):
    """Leaky relu with divisor b (default 5)."""

    def __init__(self, cfg=()):
        self.b = 5.0
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "b":
            self.b = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        return [_xelu(inputs[0], self.b)], state


class InsanityLayer(Layer):
    """Randomized leaky relu (RReLU): slope divisor ~ U[lb, ub] during
    training, (lb+ub)/2 at inference, with the reference's cumulative
    bound-annealing between calm_start and calm_end steps
    (insanity_layer-inl.hpp:49-77). Annealed bounds live in layer state
    so the update stays functional under jit."""

    def __init__(self, cfg=()):
        self.lb = 5.0
        self.ub = 10.0
        self.calm_start = 0
        self.calm_end = 0
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "lb":
            self.lb = float(val)
        if name == "ub":
            self.ub = float(val)
        if name == "calm_start":
            self.calm_start = int(val)
        if name == "calm_end":
            self.calm_end = int(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_state(self) -> Dict[str, jnp.ndarray]:
        return {
            "lb": jnp.float32(self.lb),
            "ub": jnp.float32(self.ub),
            "step": jnp.int32(0),
        }

    def forward(self, params, state, inputs, is_train, rng):
        x = inputs[0]
        lb, ub, step = state["lb"], state["ub"], state["step"]
        if self.calm_end > self.calm_start:
            # delta computed from *initial* bounds (insanity:57-60)
            delta = jnp.float32(
                (self.ub - (self.ub + self.lb) / 2.0)
                / (self.calm_end - self.calm_start))
            active = jnp.logical_and(step > self.calm_start,
                                     step < self.calm_end)
            ub = jnp.where(active, ub - delta * step, ub)
            lb = jnp.where(active, lb + delta * step, lb)
            step = jnp.where(active, step + 1, step)
        if is_train:
            assert rng is not None, "insanity layer needs an rng in training"
            mask = jax.random.uniform(rng, x.shape) * (ub - lb) + lb
            out = _xelu(x, jax.lax.stop_gradient(mask))
        else:
            out = _xelu(x, (lb + ub) / 2.0)
        new_state = dict(state, lb=lb, ub=ub, step=step)
        return [out], new_state


@jax.custom_vjp
def _prelu(x, mask):
    return jnp.where(x > 0, x, x * mask)


def _prelu_fwd(x, mask):
    return _prelu(x, mask), (x, mask)


def _prelu_bwd(res, g):
    x, mask = res
    dx = jnp.where(x > 0, g, mask * g)
    # reference gslope = sum(prelu_grad(in) * dout) with prelu_grad(a)=
    # a if a<0 else 0 — deliberately ignores the clamp and train noise
    # (prelu_layer-inl.hpp:139-158); keep that exact behavior.
    dmask = jnp.where(x < 0, x, 0.0) * g
    return dx, dmask


_prelu.defvjp(_prelu_fwd, _prelu_bwd)


class PReluLayer(Layer):
    """Learned per-channel (or per-feature) negative slope + train noise."""

    def __init__(self, cfg=()):
        self.init_slope = 0.25
        self.init_random = 0
        self.random = 0.0
        self.channel = 0
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "init_slope":
            self.init_slope = float(val)
        if name == "random_slope":
            self.init_random = int(val)
        if name == "random":
            self.random = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.channel = s.x if s.is_mat else s.ch
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        if self.init_random == 0:
            slope = jnp.full((self.channel,), self.init_slope, jnp.float32)
        else:
            slope = jax.random.uniform(key, (self.channel,)) * self.init_slope
        # tag 'bias' mirrors the reference visitor tag (prelu:61-63) so
        # bias-scoped updater params apply to the slope.
        return {"bias": slope}

    def forward(self, params, state, inputs, is_train, rng):
        x = inputs[0]
        slope = params["bias"]          # broadcasts over trailing dim
        mask = jnp.broadcast_to(slope, x.shape)
        if is_train and self.random > 0:
            assert rng is not None
            noise = jax.random.uniform(rng, x.shape) * self.random * 2.0 \
                - self.random
            mask = mask * (1.0 + noise)
        mask = jnp.clip(mask, 0.0, 1.0)
        return [_prelu(x, mask)], state


class DropoutLayer(Layer):
    """Inverted dropout; identity at inference. Self-loop layer."""

    self_loop = True

    def __init__(self, cfg=()):
        self.threshold = 0.0
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "threshold":
            self.threshold = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        if not (0.0 <= self.threshold < 1.0):
            raise ValueError("dropout: invalid threshold")
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        x = inputs[0]
        if not is_train or self.threshold == 0.0:
            return [x], state
        assert rng is not None, "dropout needs an rng in training"
        pkeep = 1.0 - self.threshold
        mask = (jax.random.uniform(rng, x.shape) < pkeep).astype(x.dtype) \
            / x.dtype.type(pkeep)
        return [x * mask], state


class ConcatLayer(Layer):
    """n-to-1 concat. dim=3 ('concat') joins features (x); dim=1
    ('ch_concat') joins channels — reference NCHW dims."""

    def __init__(self, dim: int, cfg=()):
        self.dim = dim
        super().__init__(cfg)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        if len(in_shapes) < 2:
            raise ValueError("concat: needs more than one input")
        base = in_shapes[0]
        total = 0
        for s in in_shapes:
            # ref checks all non-concat dims equal (concat_layer:22-30)
            ref = (s.ch, s.y, s.x)
            b0 = (base.ch, base.y, base.x)
            for j, (a, b) in enumerate(zip(ref, b0)):
                nchw_dim = j + 1
                if nchw_dim != self.dim and a != b:
                    raise ValueError("concat: shape mismatch")
            total += ref[self.dim - 1]
        out = list(base)
        out[self.dim - 1] = total
        self.in_shapes = list(in_shapes)
        self.out_shapes = [Shape3(*out)]
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        if inputs[0].ndim == 2:
            if self.dim != 3:
                raise ValueError("ch_concat on matrix nodes is unsupported")
            return [jnp.concatenate(inputs, axis=1)], state
        axis = {1: 3, 2: 1, 3: 2}[self.dim]   # NCHW dim -> NHWC axis
        return [jnp.concatenate(inputs, axis=axis)], state


class SplitLayer(Layer):
    """1-to-n duplicate; autodiff sums the gradients (split_layer:33-44)."""

    def __init__(self, n_out: int = 2, cfg=()):
        self.n_out = n_out
        super().__init__(cfg)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.in_shapes = [s]
        self.out_shapes = [s] * self.n_out
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        return [inputs[0]] * self.n_out, state


class FixConnectLayer(Layer):
    """Fixed (non-learned) sparse connection matrix from a text file:
    header 'nrow ncol nnz' then 'row col value' triples, where the matrix
    is (num_hidden, num_input) in reference convention."""

    def __init__(self, cfg=()):
        self.fname_weight = ""
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "fixconn_weight":
            self.fname_weight = val

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        if not s.is_mat:
            raise ValueError("fixconn: input must be a matrix")
        if self.param.num_hidden <= 0:
            raise ValueError("fixconn: must set nhidden correctly")
        if not self.fname_weight:
            raise ValueError("fixconn: must specify fixconn_weight")
        self.in_shapes = [s]
        self.out_shapes = [Shape3(1, 1, self.param.num_hidden)]
        w = np.zeros((self.param.num_hidden, s.x), np.float32)
        with open_stream(self.fname_weight, "r") as f:
            toks = f.read().split()
        nrow, ncol, nnz = int(toks[0]), int(toks[1]), int(toks[2])
        if (nrow, ncol) != w.shape:
            raise ValueError("fixconn: weight shape does not match")
        vals = toks[3:3 + 3 * nnz]
        for t in range(nnz):
            r, c = int(vals[3 * t]), int(vals[3 * t + 1])
            w[r, c] = float(vals[3 * t + 2])
        self._w = jnp.asarray(w.T)      # store (in, out) like fullc
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        return [jnp.dot(inputs[0], jax.lax.stop_gradient(self._w),
                        preferred_element_type=jnp.float32)], state
