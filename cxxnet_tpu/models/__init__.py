"""Model zoo: programmatic builders for the netconfig DSL.

The framework is config-driven like the reference — a "model" is a
netconfig text (reference examples: /root/reference/example/MNIST/*.conf,
example/ImageNet/*.conf, example/kaggle_bowl/bowl.conf). These builders
generate equivalent architectures (MLP, LeNet-style conv, AlexNet,
Inception-BN/v1, kaggle-bowl net) for tests, benchmarks, and users who
prefer Python over config files.
"""

from .mnist import mnist_mlp, mnist_conv
from .alexnet import alexnet
from .inception import inception_bn, inception_bn_tiny
from .bowl import kaggle_bowl
from .kaiming import kaiming
from .kimi_vl import decoder_lm, kimi_vl_a3b, kimi_vl_a3b_tiny
from .trinity import afmoe_lm, trinity_mini, trinity_mini_tiny
from .qwen3_next import qwen3_next, qwen3_next_lm, qwen3_next_tiny
from .lfm2 import lfm2_24b_a2b, lfm2_lm, lfm2_tiny
from .mellum2 import mellum2_12b_a2_5b, mellum2_lm, mellum2_tiny

__all__ = ["mnist_mlp", "mnist_conv", "alexnet", "inception_bn",
           "inception_bn_tiny", "kaggle_bowl", "kaiming", "decoder_lm",
           "kimi_vl_a3b", "kimi_vl_a3b_tiny", "afmoe_lm", "trinity_mini",
           "trinity_mini_tiny", "qwen3_next", "qwen3_next_lm",
           "qwen3_next_tiny", "lfm2_24b_a2b", "lfm2_lm", "lfm2_tiny",
           "mellum2_12b_a2_5b", "mellum2_lm", "mellum2_tiny"]
