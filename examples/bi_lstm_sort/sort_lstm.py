"""Bidirectional LSTM that sorts integer sequences (ref:
example/bi-lstm-sort — the reference's classic seq-labeling demo:
`500 30 999 10 130` -> `10 30 130 500 999`).

TPU-native shape: one gluon HybridBlock (Embedding -> bidirectional
LSTM -> per-step Dense), trained hybridized so the whole seq model is a
single jit-compiled XLA program over the fused RNN op's lax.scan
(mxtpu/ops/rnn_ops.py). Every output position is a classification over
the vocabulary — sorting emerges from bidirectional context alone.

Run: python examples/bi_lstm_sort/sort_lstm.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx  # noqa: E402
from mxtpu import autograd, gluon  # noqa: E402
from mxtpu.gluon import nn, rnn  # noqa: E402


def make_batches(num, seq_len=5, vocab=16, seed=0):
    """(tokens, sorted_tokens) int batches; digits are vocabulary ids."""
    r = np.random.RandomState(seed)
    x = r.randint(0, vocab, (num, seq_len)).astype(np.int32)
    y = np.sort(x, axis=1).astype(np.float32)
    return x, y


class SortNet(gluon.HybridBlock):
    def __init__(self, vocab=16, hidden=64, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, 32)
            self.lstm = rnn.LSTM(hidden, num_layers=1, bidirectional=True,
                                 layout="NTC")
            self.out = nn.Dense(vocab, flatten=False)

    def hybrid_forward(self, F, tokens):
        return self.out(self.lstm(self.embed(tokens)))


def train(num=512, seq_len=5, vocab=16, batch=64, epochs=30, lr=5e-3,
          seed=0):
    x_np, y_np = make_batches(num, seq_len, vocab, seed)
    x_all = mx.nd.array(x_np, dtype="int32")
    y_all = mx.nd.array(y_np)
    net = SortNet(vocab=vocab)
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    hist = []
    for _ in range(epochs):
        total, nb = 0.0, 0
        for s in range(0, num, batch):
            xb = x_all[s:s + batch]
            yb = y_all[s:s + batch]
            with autograd.record():
                logits = net(xb)
                loss = loss_fn(logits.reshape((-1, vocab)),
                               yb.reshape((-1,))).mean()
            loss.backward()
            trainer.step(1)
            total += float(loss.asnumpy())
            nb += 1
        hist.append(total / nb)
    return net, hist


def accuracy(net, seq_len=5, vocab=16, num=128, seed=99):
    x_np, y_np = make_batches(num, seq_len, vocab, seed)
    pred = net(mx.nd.array(x_np, dtype="int32")).asnumpy().argmax(-1)
    per_tok = float((pred == y_np).mean())
    per_seq = float((pred == y_np).all(axis=1).mean())
    return per_tok, per_seq


def main():
    net, hist = train()
    tok_acc, seq_acc = accuracy(net)
    print("loss %.3f -> %.3f | token acc %.2f | full-seq acc %.2f"
          % (hist[0], hist[-1], tok_acc, seq_acc))
    x_np, _ = make_batches(1, seed=7)
    pred = net(mx.nd.array(x_np, dtype="int32")).asnumpy().argmax(-1)
    print("input :", x_np[0].tolist())
    print("sorted:", pred[0].tolist())


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
