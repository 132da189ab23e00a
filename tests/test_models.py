"""Model zoo smoke tests: every builder config parses, shape-infers, and
runs a train step at tiny batch."""

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.models import (alexnet, inception_bn, kaggle_bowl,
                               kaiming, mnist_conv, mnist_mlp)
from cxxnet_tpu.nnet.net import FuncNet
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.graph import NetGraph
from cxxnet_tpu.utils.config import parse_config


def _shapes(conf):
    g = NetGraph()
    g.configure(parse_config(conf))
    net = FuncNet(g, g.batch_size)
    return g, net


def test_mnist_mlp_shapes():
    g, net = _shapes(mnist_mlp())
    assert net.node_shapes[-1].x == 10


def test_mnist_conv_shapes():
    g, net = _shapes(mnist_conv())
    # conv 3x3 pad1 stride2 on 28 -> 14; pool 3 stride2 ceil -> 7
    assert net.node_shapes[1] == (32, 14, 14)
    assert net.node_shapes[2] == (32, 7, 7)
    assert net.node_shapes[3].x == 32 * 7 * 7


def test_alexnet_shapes():
    g, net = _shapes(alexnet())
    # canonical AlexNet shapes (conv1 55, pool1 27, pool2 13, pool5 6)
    assert net.node_shapes[1] == (96, 55, 55)
    assert net.node_shapes[3] == (96, 27, 27)
    assert net.node_shapes[7] == (256, 13, 13)
    assert net.node_shapes[15] == (256, 6, 6)
    assert net.node_shapes[-1].x == 1000


def test_inception_bn_shapes():
    g, net = _shapes(inception_bn())
    # global avg pool collapses to 1x1; softmax over 1000
    gap = net.node_shapes[g.node_name_map["gap"]]
    assert (gap.y, gap.x) == (1, 1)
    assert net.node_shapes[-1].x == 1000
    assert len(g.layers) > 100


def test_kaggle_bowl_shapes():
    g, net = _shapes(kaggle_bowl())
    assert net.node_shapes[-1].x == 121


def test_kaiming_shapes():
    g, net = _shapes(kaiming())
    # He-J' at 224: stem 7x7/2 -> 109, pool3/1 ceil -> 107; stage pools
    # land at 35 and 16; conv11 (2x2 pad1 over the 5-wide conv10 map)
    # gives 6; SPP concat = 256*(36+9+4+1) = 12800
    assert net.node_shapes[1] == (64, 109, 109)
    assert net.node_shapes[3] == (64, 107, 107)
    assert net.node_shapes[12] == (128, 35, 35)
    assert net.node_shapes[21] == (256, 16, 16)
    assert net.node_shapes[24] == (256, 6, 6)
    assert net.node_shapes[38].x == 12800
    assert net.node_shapes[-1].x == 1000


@pytest.mark.parametrize("conf_fn,shape,nclass", [
    (lambda: alexnet(nclass=10, batch_size=4, image_size=67), (4, 67, 67, 3), 10),
    (lambda: kaggle_bowl(nclass=5, batch_size=4), (4, 40, 40, 3), 5),
    (lambda: mnist_conv(batch_size=4), (4, 28, 28, 1), 10),
    # 208 is near the smallest size where the SPP k6 pool still sees >=6
    # pixels (the reference's pre-pad "kernel size exceed input" check)
    (lambda: kaiming(nclass=10, batch_size=2, image_size=208), (2, 208, 208, 3), 10),
])
def test_models_train_step(conf_fn, shape, nclass):
    t = NetTrainer(parse_config(conf_fn()))
    t.init_model()
    rng = np.random.RandomState(0)
    data = rng.rand(*shape).astype(np.float32)
    label = rng.randint(0, nclass, (shape[0], 1)).astype(np.float32)
    t.update(DataBatch(data=data, label=label))
    assert np.isfinite(t.last_loss)


def test_inception_train_step_tiny():
    """One update of the scaled-stem BN/concat variant at 64 px (the
    full-size 224 conf trains a step in
    test_inception_bn_multidevice_real_shapes below; the 112-px conf
    can't build — stride-2 conv floor vs ceil-mode pool disagree at
    odd extents, which is why the tiny variant exists)."""
    from cxxnet_tpu.models import inception_bn_tiny
    t = NetTrainer(parse_config(inception_bn_tiny(nclass=8, batch_size=4,
                                                  image_size=64)))
    t.init_model()
    rng = np.random.RandomState(0)
    data = rng.rand(4, 64, 64, 3).astype(np.float32)
    label = rng.randint(0, 8, (4, 1)).astype(np.float32)
    t.update(DataBatch(data=data, label=label))
    assert np.isfinite(t.last_loss)


def test_inception_bn_multidevice_real_shapes():
    """Pod-config rehearsal: ONE update step of the
    full Inception-BN config at 224x224 batch 32 on the 8-device
    virtual mesh (dp=4 x tp=2), asserting finite loss and that the
    intended shardings actually materialized."""
    import jax
    from cxxnet_tpu.parallel import make_mesh

    mesh = make_mesh(4, 2)
    conf = parse_config(inception_bn(nclass=1000, batch_size=32,
                                     image_size=224)) \
        + [("model_parallel_min", "512"), ("shard_optimizer", "1")]
    t = NetTrainer(conf, mesh=mesh)
    t.init_model()
    rng = np.random.RandomState(0)
    data = rng.rand(32, 224, 224, 3).astype(np.float32)
    label = rng.randint(0, 1000, (32, 1)).astype(np.float32)
    t.update(DataBatch(data=data, label=label))
    assert np.isfinite(t.last_loss), "non-finite loss on full config"
    # batch is sharded over 'data'; the big fc weight over 'model'
    fc = t.params["fc1"]["wmat"]
    assert tuple(fc.sharding.spec) == (None, "model"), fc.sharding
    # ZeRO-1: momentum of a data-shardable weight lives on 'data'
    m = t.opt_state["fc1"]["wmat"]["m_w"]
    assert tuple(m.sharding.spec)[0] == "data", m.sharding
