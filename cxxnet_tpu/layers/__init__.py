"""Layer registry: string type -> layer factory.

Mirrors the reference's string->type registry + factory
(``/root/reference/src/layer/layer.h:324-365``,
``layer_impl-inl.hpp:36-77``), including the vestigial types that the
reference registers but cannot construct (``maxout``, ``softplus`` maps
via the enum but has no factory case — configuring them errors, matching
``layer_impl-inl.hpp``; we support softplus since our factory covers it).

``pairtest-A-B`` is a real layer type (layer.h:316-317,358-362 encodes
master*1024+slave; we parse the string directly): master and slave run
side by side, divergence is tracked in layer state — see pairtest.py.
The NumPy-reference comparisons in ``tests/test_layers.py`` complement
it for gradient checks.
"""

from __future__ import annotations

from typing import Callable, Dict, Sequence, Tuple

from .base import (Layer, LayerParam, SeqShape, Shape3, array_shape, as_mat,
                   seq_shape)
from .common import (ActivationLayer, BiasLayer, ConcatLayer, DropoutLayer,
                     FixConnectLayer, FlattenLayer, FullConnectLayer,
                     InsanityLayer, PReluLayer, SplitLayer, XeluLayer)
from .conv import (BatchNormLayer, ConvolutionLayer, InsanityPoolingLayer,
                   LRNLayer, PoolingLayer)
from .loss import LossLayer, LpLossLayer, MultiLogisticLayer, SoftmaxLayer
from .pairtest import PairTestLayer
from .sequence import (AddLayer, EmbedLayer, GatedConvLayer,
                       GatedDeltaLayer, GQAAttentionLayer, MLAAttentionLayer,
                       MoELayer, RMSNormLayer, SwiGLULayer)
from .torch_adapter import TorchLayer

_FACTORY: Dict[str, Callable[..., Layer]] = {
    "fullc": lambda cfg, **kw: FullConnectLayer(cfg),
    "fixconn": lambda cfg, **kw: FixConnectLayer(cfg),
    "bias": lambda cfg, **kw: BiasLayer(cfg),
    "softmax": lambda cfg, **kw: SoftmaxLayer(cfg),
    "relu": lambda cfg, **kw: ActivationLayer("relu", cfg),
    "sigmoid": lambda cfg, **kw: ActivationLayer("sigmoid", cfg),
    "tanh": lambda cfg, **kw: ActivationLayer("tanh", cfg),
    "softplus": lambda cfg, **kw: ActivationLayer("softplus", cfg),
    "flatten": lambda cfg, **kw: FlattenLayer(cfg),
    "dropout": lambda cfg, **kw: DropoutLayer(cfg),
    "conv": lambda cfg, **kw: ConvolutionLayer(cfg),
    "max_pooling": lambda cfg, **kw: PoolingLayer("max", cfg),
    "sum_pooling": lambda cfg, **kw: PoolingLayer("sum", cfg),
    "avg_pooling": lambda cfg, **kw: PoolingLayer("avg", cfg),
    "relu_max_pooling": lambda cfg, **kw: PoolingLayer("max", cfg,
                                                       pre_relu=True),
    "lrn": lambda cfg, **kw: LRNLayer(cfg),
    "concat": lambda cfg, **kw: ConcatLayer(3, cfg),
    "ch_concat": lambda cfg, **kw: ConcatLayer(1, cfg),
    "xelu": lambda cfg, **kw: XeluLayer(cfg),
    "split": lambda cfg, n_out=2, **kw: SplitLayer(n_out, cfg),
    "insanity": lambda cfg, **kw: InsanityLayer(cfg),
    "rrelu": lambda cfg, **kw: InsanityLayer(cfg),
    "insanity_max_pooling": lambda cfg, **kw: InsanityPoolingLayer("max", cfg),
    "lp_loss": lambda cfg, **kw: LpLossLayer(cfg),
    "l2_loss": lambda cfg, **kw: LpLossLayer(cfg),
    "multi_logistic": lambda cfg, **kw: MultiLogisticLayer(cfg),
    "prelu": lambda cfg, **kw: PReluLayer(cfg),
    "batch_norm": lambda cfg, **kw: BatchNormLayer(True, cfg),
    "batch_norm_no_ma": lambda cfg, **kw: BatchNormLayer(False, cfg),
    # cross-framework oracle (the caffe adapter equivalent): a torch-
    # backed fullc/conv for pairtest-conv-torch style in-net A/B checks
    "torch": lambda cfg, **kw: TorchLayer(cfg),
    # the sequence node (batch, time, features) and the decoder block
    # over it (layers/sequence.py, doc/sequence.md)
    "embed": lambda cfg, **kw: EmbedLayer(cfg),
    "rmsnorm": lambda cfg, **kw: RMSNormLayer(cfg),
    "add": lambda cfg, **kw: AddLayer(cfg),
    "swiglu": lambda cfg, **kw: SwiGLULayer(cfg),
    "mla_attention": lambda cfg, **kw: MLAAttentionLayer(cfg),
    "gqa_attention": lambda cfg, **kw: GQAAttentionLayer(cfg),
    "gated_delta": lambda cfg, **kw: GatedDeltaLayer(cfg),
    "gated_conv": lambda cfg, **kw: GatedConvLayer(cfg),
    "moe": lambda cfg, **kw: MoELayer(cfg),
}

# registered in the reference enum but rejected by its factory
_VESTIGIAL = ("maxout",)


def known_layer_type(type_str: str) -> bool:
    if type_str.startswith("pairtest-"):
        a, _, b = type_str[len("pairtest-"):].partition("-")
        return known_layer_type(a) and known_layer_type(b)
    return type_str in _FACTORY or type_str in _VESTIGIAL


def create_layer(type_str: str, cfg: Sequence[Tuple[str, str]] = (),
                 **kwargs) -> Layer:
    """Create a layer from its config-file type string."""
    if type_str.startswith("pairtest-"):
        a, _, b = type_str[len("pairtest-"):].partition("-")
        if not a or not b:
            raise ValueError("pairtest type must be pairtest-<master>-<slave>")
        cfg = list(cfg)
        shared = [(n, v) for n, v in cfg
                  if not n.startswith(("master:", "slave:"))]
        master = create_layer(a, shared + [
            (n[len("master:"):], v) for n, v in cfg
            if n.startswith("master:")], **kwargs)
        slave = create_layer(b, shared + [
            (n[len("slave:"):], v) for n, v in cfg
            if n.startswith("slave:")], **kwargs)
        return PairTestLayer(master, slave)
    if type_str in _VESTIGIAL:
        raise ValueError(
            "layer type %r is registered but has no implementation "
            "(matches reference factory behavior)" % type_str)
    if type_str not in _FACTORY:
        raise ValueError("unknown layer type: %r" % type_str)
    return _FACTORY[type_str](list(cfg), **kwargs)


__all__ = [
    "Layer", "LayerParam", "SeqShape", "Shape3", "array_shape", "as_mat",
    "seq_shape",
    "create_layer", "known_layer_type", "LossLayer",
]
