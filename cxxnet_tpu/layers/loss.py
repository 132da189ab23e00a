"""Loss layers: softmax, Lp regression, elementwise logistic.

Reference loss layers are self-loop layers that (1) transform the node in
Forward and (2) overwrite it with the gradient in Backprop, scaled by
``grad_scale / (batch_size * update_period)``
(loss/loss_layer_base-inl.hpp:37-66). Here each loss layer provides

- ``forward``: the prediction transform (softmax probs / identity /
  sigmoid) — what Predict and Extract observe, and
- ``loss_value``: a scalar whose ``jax.grad`` w.r.t. the *pre-transform*
  input equals the reference gradient including the grad_scale /
  batch_size scaling (the 1/update_period factor is applied by the
  trainer when an accumulation window closes, which is algebraically
  identical to the reference's per-batch pre-scaling).

The ``target`` parameter binds the loss to a named label field
(label_vec ranges, loss_layer_base:27).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .base import Layer, Shape3


class LossLayer(Layer):
    is_loss = True
    self_loop = True

    def __init__(self, cfg=()):
        self.target = "label"
        self.grad_scale = 1.0
        self.batch_size = 0          # global batch size, set by trainer cfg
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "target":
            self.target = val
        if name == "grad_scale":
            self.grad_scale = float(val)
        if name == "batch_size":
            self.batch_size = int(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def _scale(self) -> float:
        assert self.batch_size > 0, "loss layer: batch_size not set"
        return self.grad_scale / self.batch_size

    def loss_value(self, logit: jnp.ndarray, label: jnp.ndarray,
                   mask: jnp.ndarray) -> jnp.ndarray:
        """Scalar loss; mask is 1.0 for real rows, 0.0 for tail
        padding — or None when every row is real (the steady-state
        specialization skips the mask multiply)."""
        raise NotImplementedError


class SoftmaxLayer(LossLayer):
    """Softmax + cross-entropy on an integer class label (1 column).

    Logits are upcast to f32 at this boundary: in mixed-precision nets
    the activations ride bf16 and the loss is where precision returns.

    On a sequence node ``(batch, time, classes)`` the label field holds
    one class a position (``label_vec[0,time) = label``) and a row's
    loss is the mean over its positions, so the total is the mean
    cross-entropy over all positions of the batch. ``loss_chunk = n``
    takes the positions ``n`` at a time, each chunk's float32
    log-softmax recomputed in the backward pass, so that the float32
    copy of the logits and of their gradient is a chunk's, not the
    sequence's (8k positions x 20k classes: 1.3 GB each).
    """

    def __init__(self, cfg=()):
        self.loss_chunk = 0
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "loss_chunk":
            self.loss_chunk = int(val)

    @staticmethod
    def _ce_sum(logit, lab):
        """Summed cross-entropy of each row's positions: (b, t, classes),
        (b, t) -> (b,)."""
        logp = jax.nn.log_softmax(logit.astype(jnp.float32), axis=-1)
        return jnp.sum(-jnp.take_along_axis(
            logp, lab.astype(jnp.int32)[..., None], axis=-1)[..., 0], axis=-1)

    def forward(self, params, state, inputs, is_train, rng):
        return [jax.nn.softmax(inputs[0].astype(jnp.float32),
                               axis=-1)], state

    def loss_value(self, logit, label, mask):
        if logit.ndim == 3:
            t, c = logit.shape[1], self.loss_chunk
            if 0 < c < t and t % c == 0:
                part = jax.checkpoint(self._ce_sum)
                ce = sum(part(logit[:, i:i + c], label[:, i:i + c])
                         for i in range(0, t, c)) / t
            else:
                ce = self._ce_sum(logit, label) / t
        else:
            lab = label[:, 0].astype(jnp.int32)
            logp = jax.nn.log_softmax(logit.astype(jnp.float32), axis=-1)
            ce = -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]
        if mask is not None:
            ce = ce * mask
        return self._scale() * jnp.sum(ce)


class LpLossLayer(LossLayer):
    """Lp regression loss against a dense label block (p default 2).

    Reference gradient: p * |x-l|^(p-1) * sign(x-l) * scale
    (lp_loss_layer-inl.hpp:31-40) == grad of |x-l|^p * scale.
    """

    def __init__(self, cfg=()):
        self.p = 2.0
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "p":
            self.p = float(val)

    def forward(self, params, state, inputs, is_train, rng):
        return [inputs[0].astype(jnp.float32)], state

    def loss_value(self, logit, label, mask):
        d = jnp.abs(logit.astype(jnp.float32) - label)
        if self.p == 2.0:
            lp = d * d
        elif self.p == 1.0:
            lp = d
        else:
            lp = jnp.power(d, self.p)
        row = jnp.sum(lp, axis=-1)
        if mask is not None:
            row = row * mask
        return self._scale() * jnp.sum(row)


class MultiLogisticLayer(LossLayer):
    """Elementwise sigmoid + binary cross-entropy per output (multi-label).

    Reference gradient is sigmoid(x) - label (multi_logistic:25-34) ==
    grad of BCE w.r.t. the logit.
    """

    def forward(self, params, state, inputs, is_train, rng):
        return [jax.nn.sigmoid(inputs[0].astype(jnp.float32))], state

    def loss_value(self, logit, label, mask):
        logit = logit.astype(jnp.float32)
        # numerically stable BCE-with-logits
        bce = jnp.maximum(logit, 0) - logit * label \
            + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        row = jnp.sum(bce, axis=-1)
        if mask is not None:
            row = row * mask
        return self._scale() * jnp.sum(row)
