"""Word-level language model example (ref: example/gluon/
word_language_model/train.py — LSTM LM over PTB, the reference's config-2
benchmark workload).

2-layer LSTM over an embedded token stream, truncated-BPTT training with
gradient clipping and perplexity reporting. Runs on a synthetic
Zipf-distributed corpus by default (no dataset egress here); pass --text
with a tokenized file for real data.

Usage:
    python examples/gluon/word_language_model.py --epochs 2
"""
import argparse
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def batchify(tokens, batch_size):
    n = len(tokens) // batch_size
    return np.asarray(tokens[:n * batch_size], np.int32) \
        .reshape(batch_size, n).T  # (time, batch)


def synthetic_corpus(vocab, length, seed=0):
    rng = np.random.RandomState(seed)
    # Zipf-ish unigram stream with local correlations (bigram-ish repeats)
    base = rng.zipf(1.3, size=length) % vocab
    rep = rng.uniform(size=length) < 0.3
    base[1:][rep[1:]] = base[:-1][rep[1:]]
    return base.astype(np.int32)


class RNNModel:
    def __init__(self, vocab, embed, hidden, layers, dropout, dtype):
        from mxtpu import gluon
        from mxtpu.gluon import nn, rnn

        self.net = nn.HybridSequential()
        self.embedding = nn.Embedding(vocab, embed)
        self.lstm = rnn.LSTM(hidden, num_layers=layers, dropout=dropout)
        self.decoder = nn.Dense(vocab, flatten=False)
        for blk in (self.embedding, self.lstm, self.decoder):
            self.net.add(blk)
        self.net.initialize()
        if dtype != "float32":
            self.net.cast(dtype)
        self.dtype = dtype

    def __call__(self, x, state):
        emb = self.embedding(x)
        out, state = self.lstm(emb, state)
        return self.decoder(out), state

    def begin_state(self, batch_size):
        return self.lstm.begin_state(batch_size=batch_size,
                                     dtype=self.dtype)

    def collect_params(self):
        return self.net.collect_params()


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--bptt", type=int, default=35)
    p.add_argument("--vocab", type=int, default=10000)
    p.add_argument("--embed", type=int, default=650)
    p.add_argument("--hidden", type=int, default=650)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--dropout", type=float, default=0.5)
    p.add_argument("--lr", type=float, default=1.0)
    p.add_argument("--clip", type=float, default=0.25)
    p.add_argument("--corpus-len", type=int, default=40000)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--text", default="",
                   help="path to a whitespace-tokenized corpus file")
    args = p.parse_args(argv)

    from mxtpu import autograd, gluon
    import mxtpu as mx

    if args.text:
        with open(args.text) as f:
            words = f.read().split()
        vocab_map = {}
        tokens = np.asarray([vocab_map.setdefault(w, len(vocab_map))
                             for w in words], np.int32)
        args.vocab = len(vocab_map)
    else:
        tokens = synthetic_corpus(args.vocab, args.corpus_len)

    data = batchify(tokens, args.batch_size)
    model = RNNModel(args.vocab, args.embed, args.hidden, args.layers,
                     args.dropout, args.dtype)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(model.collect_params(), "sgd",
                            {"learning_rate": args.lr})
    params = [p_ for p_ in model.collect_params().values()
              if p_.grad_req != "null"]

    for epoch in range(args.epochs):
        total_loss, total_tok = 0.0, 0
        state = model.begin_state(args.batch_size)
        tic = time.time()
        for i in range(0, data.shape[0] - 1 - args.bptt, args.bptt):
            x = mx.nd.array(data[i:i + args.bptt])
            y = mx.nd.array(data[i + 1:i + 1 + args.bptt].reshape(-1))
            state = [s.detach() for s in state]  # truncated BPTT
            with autograd.record():
                out, state = model(x, state)
                loss = loss_fn(out.reshape((-1, args.vocab)), y)
            loss.backward()
            gluon.utils.clip_global_norm(
                [p_.grad() for p_ in params],
                args.clip * args.bptt * args.batch_size)
            trainer.step(args.bptt * args.batch_size)
            ntok = args.bptt * args.batch_size
            total_loss += float(loss.mean().asnumpy()) * ntok
            total_tok += ntok
        ppl = math.exp(min(total_loss / max(total_tok, 1), 20))
        print("epoch %d: ppl %.1f  %.0f tokens/s"
              % (epoch, ppl, total_tok / (time.time() - tic)), flush=True)


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
