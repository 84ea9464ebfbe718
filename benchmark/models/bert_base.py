"""``TransformerLM(causal=False)`` at BERT-Base's sizes under the whole-step
trainer, the loss over every position, as ``bench.py`` builds it."""
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.transformer import TransformerLM

from . import common


def build(cfg, specs, leaves):
    net = TransformerLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],
        max_len=cfg["max_position_embeddings"],
        hidden_mult=cfg["intermediate_size"] // cfg["hidden_size"],
        causal=False)
    net.cast(cfg["dtype"])
    return common.load_leaves(net, specs, leaves)


def train_step(cfg, net, optimizer):
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    vocab = cfg["vocab_size"]

    def forward(block, tokens, labels):
        return loss(block(tokens).reshape((-1, vocab)),
                    labels.reshape((-1,)))

    return common.whole_step(net, None, optimizer, forward=forward)


def batch(cfg, x, y):
    return mx.nd.NDArray(x), mx.nd.NDArray(y)
