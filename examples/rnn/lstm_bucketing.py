"""Variable-length LSTM language model with bucketing — the classic
mx.rnn + BucketingModule workflow (ref: example/rnn/bucketing/
lstm_bucketing.py), on synthetic token data so it runs offline.

The legacy symbolic cells compose one unrolled Symbol per bucket length
(sym_gen); BucketingModule compiles one executor per bucket and shares
parameters across them. On this engine each bucket's graph jits once —
XLA sees the fully unrolled program per length, the TPU-native stand-in
for the reference's fused cudnn path.

Run: python examples/rnn/lstm_bucketing.py [--epochs 3]
"""
import argparse

import numpy as np

import mxtpu as mx
from mxtpu import rnn
from mxtpu.module import BucketingModule


def synthetic_sentences(vocab, n=200, seed=0):
    """Token sequences with a DETERMINISTIC learnable pattern (next
    token = prev+1 mod vocab) in three length buckets — perplexity can
    approach 1 once learned."""
    rng = np.random.RandomState(seed)
    sentences = []
    for _ in range(n):
        length = int(rng.choice([6, 10, 14]))
        start = int(rng.randint(1, vocab))
        s = [(start + i) % (vocab - 1) + 1 for i in range(length)]
        sentences.append(s)
    return sentences


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--num-hidden", type=int, default=32)
    ap.add_argument("--num-embed", type=int, default=16)
    ap.add_argument("--num-layers", type=int, default=1)
    ap.add_argument("--vocab", type=int, default=32)
    ns = ap.parse_args()

    buckets = [6, 10, 14]
    sents = synthetic_sentences(ns.vocab)
    # BucketSentenceIter derives labels itself (data shifted left by one)
    data_train = rnn.BucketSentenceIter(
        sents, ns.batch_size, buckets=buckets, invalid_label=0)

    stack = rnn.SequentialRNNCell()
    for i in range(ns.num_layers):
        stack.add(rnn.LSTMCell(num_hidden=ns.num_hidden,
                               prefix="lstm_l%d_" % i))

    def sym_gen(seq_len):
        data = mx.sym.var("data")
        label = mx.sym.var("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=ns.vocab,
                                 output_dim=ns.num_embed, name="embed")
        stack.reset()
        outputs, _ = stack.unroll(
            seq_len, inputs=embed,
            begin_state=stack.begin_state(batch_size=ns.batch_size),
            merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, ns.num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=ns.vocab,
                                     name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(pred, label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    model = BucketingModule(sym_gen,
                            default_bucket_key=data_train.default_bucket_key)
    metric = mx.metric.Perplexity(ignore_label=0)
    model.fit(train_data=data_train, eval_metric=metric,
              optimizer="sgd",
              # SoftmaxOutput grads are summed over batch*seq rows, so
              # the lr is small (the reference example trains at 0.01)
              optimizer_params={"learning_rate": 0.02, "momentum": 0.9},
              initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
              num_epoch=ns.epochs)
    metric.reset()
    model.score(data_train, metric)
    name, ppl = metric.get()
    print("final %s: %.2f" % (name, ppl))
    return ppl


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
