"""CNN for sentence classification, Kim-2014 style
(ref: example/cnn_text_classification/text_cnn.py — embedding, parallel
conv branches of several filter widths, max-over-time pooling, concat,
dropout, dense).

Data is a hermetic synthetic task with real signal: class = which of two
"keyword" token groups dominates the sentence. Swap ``make_data`` for a
real tokenized corpus to reproduce the reference's MR/SST workflow.

    python examples/cnn_text_classification/text_cnn.py --epochs 3
"""
import argparse

import numpy as np

import mxtpu as mx
from mxtpu import autograd, gluon
from mxtpu.gluon import nn
from mxtpu.gluon.block import HybridBlock


class TextCNN(HybridBlock):
    def __init__(self, vocab, embed, num_filter, widths, classes,
                 dropout, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.embedding = nn.Embedding(vocab, embed)
            self.branches = []
            for i, w in enumerate(widths):
                conv = nn.Conv1D(num_filter, w, activation="relu",
                                 prefix="conv%d_" % i)
                # NCW layout: Conv1D contracts over (embed, width)
                self.register_child(conv)
                self.branches.append(conv)
            self.dropout = nn.Dropout(dropout)
            self.fc = nn.Dense(classes)

    def hybrid_forward(self, F, tokens):
        # (batch, seq) -> (batch, seq, embed) -> (batch, embed, seq)
        e = self.embedding(tokens).transpose((0, 2, 1))
        pooled = [F.max(br(e), axis=2) for br in self.branches]
        return self.fc(self.dropout(F.concat(*pooled, dim=1)))


def make_data(rng, n, vocab, seq, classes, keywords):
    """Sentences of random tokens; each class has a 3-token keyword set
    (SHARED between train and val — the signal to learn), and the label
    is the class whose keywords were injected."""
    # background tokens exclude every class's keywords — the label is
    # then EXACTLY "which keywords were injected", as documented
    bg = np.setdiff1d(np.arange(10, vocab), keywords.ravel())
    x = bg[rng.randint(0, len(bg), (n, seq))]
    y = rng.randint(0, classes, n)
    for i in range(n):
        kws = keywords[y[i]]
        pos = rng.choice(seq, 4, replace=False)
        x[i, pos] = kws[rng.randint(0, 3, 4)]
    return x.astype(np.int32), y.astype(np.float32)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--vocab", type=int, default=200)
    p.add_argument("--seq-len", type=int, default=24)
    p.add_argument("--embed", type=int, default=32)
    p.add_argument("--num-filter", type=int, default=16)
    p.add_argument("--widths", default="2,3,4")
    p.add_argument("--classes", type=int, default=3)
    p.add_argument("--dropout", type=float, default=0.2)
    p.add_argument("--lr", type=float, default=5e-3)
    p.add_argument("--train-size", type=int, default=1024)
    args = p.parse_args()

    rng = np.random.RandomState(0)
    widths = [int(w) for w in args.widths.split(",")]
    keywords = rng.choice(np.arange(10, args.vocab), (args.classes, 3),
                          replace=False)
    tx, ty = make_data(rng, args.train_size, args.vocab, args.seq_len,
                       args.classes, keywords)
    vx, vy = make_data(rng, max(args.train_size // 4, args.batch_size),
                       args.vocab, args.seq_len, args.classes, keywords)

    net = TextCNN(args.vocab, args.embed, args.num_filter, widths,
                  args.classes, args.dropout)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})

    b = args.batch_size
    acc = 0.0
    for epoch in range(args.epochs):
        cum, nb = 0.0, 0
        for i in range(0, len(tx) - b + 1, b):
            data = mx.nd.array(tx[i:i + b], dtype="int32")
            label = mx.nd.array(ty[i:i + b])
            with autograd.record():
                loss = loss_fn(net(data), label)
            loss.backward()
            trainer.step(b)
            cum += float(loss.mean().asnumpy())
            nb += 1
        metric = mx.metric.Accuracy()
        for i in range(0, len(vx) - b + 1, b):
            metric.update([mx.nd.array(vy[i:i + b])],
                          [net(mx.nd.array(vx[i:i + b], dtype="int32"))])
        acc = metric.get()[1]
        print("epoch %d loss %.4f val-acc %.4f"
              % (epoch, cum / max(nb, 1), acc))
    return acc


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
