"""``gluon.model_zoo.vision.resnet50_v1`` under ``mx.layout("NHWC")``, as a
user of the library builds and trains it."""
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo import vision

from . import common


def build(cfg, specs, leaves):
    with mx.layout("NHWC"):
        net = getattr(vision, cfg["zoo_model"])(classes=cfg["classes"])
    net.cast(cfg["dtype"])
    return common.load_leaves(net, specs, leaves)


def train_step(cfg, net, optimizer):
    return common.whole_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             optimizer)


def batch(cfg, x, y):
    return mx.nd.NDArray(x), mx.nd.NDArray(y)

