"""Layer base types for the TPU-native layer zoo.

Layers are *pure functions over pytrees* — no in-place node mutation, no
device threads. The reference's hand-written backprop per layer
(``/root/reference/src/layer/layer.h:163-280``) is replaced by ``jax.grad``
through the forward computation, with ``jax.custom_vjp`` only where the
reference's gradient deliberately differs from the true gradient of its
forward (e.g. PReLU's slope gradient ignoring the clamp, see common.py).

Tensor layout is TPU-first: spatial nodes are NHWC ``(batch, y, x, ch)``
so convolutions feed the MXU without transposes; flattened nodes are 2-D
``(batch, features)`` so the feature dim is the TPU lane dim. Logical
node shapes keep the reference's ``(ch, y, x)`` convention
(``layer.h:32-72``) so config files and shape messages stay compatible:
a logical shape with ch==1 and y==1 is a "matrix" node stored 2-D.
A third kind is the sequence node ``(batch, time, features)``
(``SeqShape``): ``y`` counts the positions, ``x`` the features of each,
``ch`` is 1. An ``embed`` layer makes one from a matrix of integer ids;
``doc/sequence.md`` lists the layers that read and write it.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Shape3(NamedTuple):
    """Logical node shape without batch: (ch, y, x) — reference convention."""
    ch: int
    y: int
    x: int

    @property
    def is_mat(self) -> bool:
        # reference Node::is_mat(): size(1)==1 && size(2)==1 (layer.h:60-63)
        return self.ch == 1 and self.y == 1

    @property
    def is_seq(self) -> bool:
        return False

    @property
    def flat_size(self) -> int:
        return self.ch * self.y * self.x


class SeqShape(Shape3):
    """Logical shape of a sequence node: ``(1, time, features)``, stored
    ``(batch, time, features)``. Never a matrix, whatever its length;
    compares equal to the ``Shape3`` of the same numbers."""
    __slots__ = ()

    @property
    def is_mat(self) -> bool:
        return False

    @property
    def is_seq(self) -> bool:
        return True


def seq_shape(time: int, features: int) -> SeqShape:
    return SeqShape(1, time, features)


# What a ``remat = block`` segment keeps of its inside, besides what
# later layers read (nnet/net.py:_run_segment): the values a layer has
# named (``jax.ad_checkpoint.checkpoint_name``) as dear to make again
# and cheap to hold. The fused attention core names its two outputs,
# ``o`` and the row log-sum-exp (layers/pallas_kernels.py:_attention_fwd):
# its backward kernel reads both, so a segment that kept neither would
# run the forward kernel a second time for them. The delta rule names
# the triangular inverse a chunk (layers/sequence.py:_solve_unit_lower):
# sixty small float32 products a layer, more than half of its scan's
# device time when a step made them three times (PERF.md, PR 34), and
# 67 MB a layer to hold in bfloat16 at 2 x 8,192 positions. Where the
# rule's scan is the fused kernels, their forward rule names the forward
# kernel's three outputs (layers/pallas_kernels.py:_gated_delta_scan_fwd:
# ``o``, a state a chunk and ``u``, which the backward kernel reads; 134
# + 268 + 134 MB a layer there), so that it runs once a step: 3.43 ->
# 3.50 sequences a second in the cell (PERF.md, PR 37). The expert layer
# names its routing (layers/sequence.py: ``MoELayer.route``,
# ``dispatch_plan``): the router's float32 logits, the picks and the
# integer plan of which row holds which pick, so that the recomputed
# forward runs no router product, ``top_k``, one-hot count or scatter
# for it (33.5 MB of logits and about 3 MB of integers a layer at
# Qwen3-Next's 512 experts and 2 x 8,192 tokens). A name outside a
# ``jax.checkpoint`` is the identity and lowers to nothing.
ATTENTION_KEEPS = ("attention_o", "attention_lse")
DELTA_KEEPS = ("delta_solve",)
DELTA_SCAN_KEEPS = ("delta_o", "delta_state", "delta_u")
MOE_KEEPS = ("moe_logits", "moe_picks", "moe_src", "moe_dest", "moe_tok",
             "moe_expert", "moe_load")
BLOCK_REMAT_KEEPS = ATTENTION_KEEPS + DELTA_KEEPS + DELTA_SCAN_KEEPS \
    + MOE_KEEPS


def array_shape(batch: int, s: Shape3) -> Tuple[int, ...]:
    """Concrete array shape for a logical node shape."""
    if s.is_mat:
        return (batch, s.x)
    if s.is_seq:
        return (batch, s.y, s.x)
    return (batch, s.y, s.x, s.ch)


def as_mat(x: jnp.ndarray) -> jnp.ndarray:
    """View a node value as (batch, features), reference Node::mat() order.

    Reference mat() flattens NCHW c-order (ch major, then y, then x); our
    spatial arrays are NHWC so we transpose before reshaping to keep
    weight layouts interchangeable with the reference convention.
    """
    if x.ndim == 2:
        return x
    b = x.shape[0]
    if x.ndim == 3:             # sequence node: time major, as stored
        return x.reshape(b, -1)
    return jnp.transpose(x, (0, 3, 1, 2)).reshape(b, -1)


@dataclass
class LayerParam:
    """Common layer hyper-parameters (reference param.h:15-139)."""
    num_hidden: int = 0
    init_sigma: float = 0.01
    init_uniform: float = -1.0
    init_sparse: int = 10
    init_bias: float = 0.0
    num_channel: int = 0
    random_type: int = 0        # 0 gaussian, 1 uniform/xavier, 2 kaiming
    num_group: int = 1
    kernel_height: int = 0
    kernel_width: int = 0
    stride: int = 1
    pad_y: int = 0
    pad_x: int = 0
    no_bias: int = 0
    temp_col_max: int = 64 << 18
    silent: int = 0
    num_input_channel: int = 0
    num_input_node: int = 0
    # TPU mixed precision: 'bfloat16' casts matmul/conv operands to
    # bf16 with f32 accumulation (MXU-native); weights/state stay f32.
    # New knob, no reference equivalent (2015-era f32-only).
    compute_dtype: str = "float32"
    # perf toggle (measurements in doc/perf_profile.md round 4):
    # bn_fold_affine folds BN's normalize+affine into one per-channel
    # scale/shift so the full-tensor math stays in the compute dtype
    # (+2.5% Inception-BN; DEFAULT — same math as the eval path's
    # folded form, reassociation-level rounding only)
    bn_fold_affine: int = 1

    def set_param(self, name: str, val: str) -> None:
        if name == "init_sigma":
            self.init_sigma = float(val)
        if name == "init_uniform":
            self.init_uniform = float(val)
        if name == "init_bias":
            self.init_bias = float(val)
        if name == "init_sparse":
            self.init_sparse = int(val)
        if name == "random_type":
            if val == "gaussian":
                self.random_type = 0
            elif val in ("uniform", "xavier"):
                self.random_type = 1
            elif val == "kaiming":
                self.random_type = 2
            else:
                raise ValueError("invalid random_type %r" % val)
        if name == "nhidden":
            self.num_hidden = int(val)
        if name == "nchannel":
            self.num_channel = int(val)
        if name == "ngroup":
            self.num_group = int(val)
        if name == "kernel_size":
            self.kernel_width = self.kernel_height = int(val)
        if name == "kernel_height":
            self.kernel_height = int(val)
        if name == "kernel_width":
            self.kernel_width = int(val)
        if name == "stride":
            self.stride = int(val)
        if name == "pad":
            self.pad_y = self.pad_x = int(val)
        if name == "pad_y":
            self.pad_y = int(val)
        if name == "pad_x":
            self.pad_x = int(val)
        if name == "no_bias":
            self.no_bias = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "temp_col_max":
            self.temp_col_max = int(val) << 18
        if name == "dtype":
            if val not in ("float32", "bfloat16"):
                raise ValueError("dtype must be float32 or bfloat16")
            self.compute_dtype = val
        if name == "bn_fold_affine":
            self.bn_fold_affine = int(val)

    def rand_init_weight(self, key: jax.Array, shape: Tuple[int, ...],
                         in_num: int, out_num: int) -> jnp.ndarray:
        """Weight init matching reference RandInitWeight (param.h:113-138)."""
        if self.random_type == 0:
            return self.init_sigma * jax.random.normal(key, shape, jnp.float32)
        if self.random_type == 1:
            a = float(np.sqrt(3.0 / (in_num + out_num)))
            if self.init_uniform > 0:
                a = self.init_uniform
            return jax.random.uniform(key, shape, jnp.float32, -a, a)
        if self.random_type == 2:
            if self.num_hidden > 0:
                sigma = float(np.sqrt(2.0 / self.num_hidden))
            else:
                sigma = float(np.sqrt(
                    2.0 / (self.num_channel * self.kernel_width
                           * self.kernel_height)))
            return sigma * jax.random.normal(key, shape, jnp.float32)
        raise ValueError("unsupported random_type %d" % self.random_type)


class Layer:
    """Base class: a declarative spec + pure forward.

    Lifecycle: construct with merged config -> ``infer_shape`` (records
    input shapes, returns output shapes; raises on inconsistency, like
    the reference's InitConnection checks) -> ``init_params`` /
    ``init_state`` -> ``forward``.
    """

    # class-level flags
    is_loss = False
    self_loop = False           # must be a self-loop connection

    def __init__(self, cfg: Sequence[Tuple[str, str]] = ()) -> None:
        self.param = LayerParam()
        self.in_shapes: List[Shape3] = []
        self.out_shapes: List[Shape3] = []
        for name, val in cfg:
            self.set_param(name, val)

    # -- config --------------------------------------------------------

    def set_param(self, name: str, val: str) -> None:
        self.param.set_param(name, val)

    # -- shape inference ------------------------------------------------

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        raise NotImplementedError

    def _expect_one(self, in_shapes: List[Shape3]) -> Shape3:
        if len(in_shapes) != 1:
            raise ValueError("%s: only supports 1-1 connection"
                             % type(self).__name__)
        return in_shapes[0]

    # -- parameters / state ---------------------------------------------

    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        """Learnable parameters; keys 'wmat'/'bias' mirror the reference
        visitor tags (visitor.h:26-165) so tag-scoped updater params and
        weight get/set keep working."""
        return {}

    def init_state(self) -> Dict[str, jnp.ndarray]:
        """Non-learnable persistent state (BN running stats, annealing)."""
        return {}

    # -- compute ---------------------------------------------------------

    #: layers that reduce over the batch dimension (batch norm) set this
    #: so FuncNet passes them the padded-row mask as a keyword
    needs_mask = False

    def forward(self, params: Dict[str, jnp.ndarray],
                state: Dict[str, jnp.ndarray],
                inputs: List[jnp.ndarray],
                is_train: bool,
                rng: Optional[jax.Array]) -> Tuple[List[jnp.ndarray],
                                                   Dict[str, jnp.ndarray]]:
        raise NotImplementedError
