"""Sparse linear classification (BASELINE.json config 5).

Reference: example/sparse/linear_classification/ — LibSVM data, a
csr x row_sparse linear model, sparse gradients, optionally a distributed
kvstore with row_sparse_pull.

TPU-native design: the forward is ``mx.nd.sparse.dot(csr_batch, weight)``
which lowers to gather + segment-sum (O(nnz) — the dense fallback would
materialize a (batch, num_features) matrix: at the reference's AVAZU scale,
8192 x 1M x 4B = 32 GB, the documented cliff). Gradients are produced
row-sparse (only touched rows), updated with the lazy sparse optimizer
path (mxtpu/optimizer.py lazy_update), and pulled back through
``kv.row_sparse_pull`` keyed by the batch's feature ids — the same
update-only-what-you-touched flow the reference runs over ps-lite.

Run: python examples/sparse/linear_classification.py [--synthetic]
"""
import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx  # noqa: E402

from mxtpu.io import LibSVMIter  # noqa: E402
from mxtpu.ndarray.sparse import RowSparseNDArray  # noqa: E402


def make_synthetic_libsvm(path, num_rows=2000, num_features=10000,
                          nnz_per_row=30, seed=0):
    """Synthetic separable-ish binary problem in LibSVM text format."""
    r = np.random.RandomState(seed)
    true_w = r.normal(0, 1, num_features)
    with open(path, "w") as f:
        for _ in range(num_rows):
            idx = np.sort(r.choice(num_features, nnz_per_row, replace=False))
            val = r.normal(0, 1, nnz_per_row)
            label = 1 if val @ true_w[idx] > 0 else 0
            toks = " ".join("%d:%.4f" % (i, v) for i, v in zip(idx, val))
            f.write("%d %s\n" % (label, toks))


def _fused_step():
    """One jitted forward+loss+grad program: logits via gather/segment-sum
    (= sparse.dot), softmax CE, per-nnz weight-grad contributions — so the
    training loop performs a SINGLE device fetch per batch. Each
    host<->device sync stalls the dispatch pipeline, and this workload's
    math is ~0.2 MFLOP/batch: the original loop's ~5 syncs/batch were its
    entire cost."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ndarray.sparse import _csr_row_ids

    @jax.jit
    def step(weight, bias, data, indices, indptr, y):
        nnz = data.shape[0]
        batch = y.shape[0]
        # padded nnz tail: row ids land past the last row; clip and rely
        # on data==0 there to contribute nothing (row derivation shared
        # with todense/csr-dot: sparse.py:_csr_row_ids)
        rows = jnp.clip(_csr_row_ids(indptr, nnz), 0, batch - 1)
        wrows = jnp.take(weight, indices, axis=0)            # (nnz, C)
        logits = jax.ops.segment_sum(data[:, None] * wrows, rows,
                                     num_segments=batch) + bias
        zmax = jnp.max(logits, axis=1, keepdims=True)
        ez = jnp.exp(logits - zmax)
        p = ez / jnp.sum(ez, axis=1, keepdims=True)
        yi = y.astype(jnp.int32)
        picked = jnp.clip(p[jnp.arange(batch), yi], 1e-12, None)
        loss = -jnp.mean(jnp.log(picked))
        correct = jnp.sum(jnp.argmax(logits, axis=1) == yi)
        d = (p - jax.nn.one_hot(yi, logits.shape[1],
                                dtype=p.dtype)) / batch
        contrib = data[:, None] * jnp.take(d, rows, axis=0)  # (nnz, C)
        return loss, correct, jnp.sum(d, axis=0), contrib

    return step


def train(data_path, num_features, batch_size=256, epochs=3, lr=0.05,
          kv=None, measure=False):
    """Train; with measure=True also returns steady-state samples/sec
    (excludes LibSVM parsing and the first, compile-heavy epoch)."""
    import time

    it = LibSVMIter(data_libsvm=data_path, data_shape=(num_features,),
                    batch_size=batch_size)
    t_start = None
    weight = mx.nd.array(np.random.RandomState(1)
                         .normal(0, 0.01, (num_features, 2))
                         .astype(np.float32))
    bias = mx.nd.zeros((2,))
    if kv is not None:
        kv.init("weight", weight)
    # lazy_update: only rows present in the row-sparse grad advance their
    # optimizer state (mxtpu/optimizer.py ~ optimizer_op.cc sparse Adam)
    updater = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=lr, lazy_update=True))
    bias_updater = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=lr))

    import jax
    import jax.numpy as jnp
    step = _fused_step()

    loss_hist = []
    measured = 0
    for ep in range(epochs):
        if measure and ep == 1:  # epoch 0 = warmup/compile
            t_start = time.perf_counter()
        if ep >= 1:
            measured += 1
        it.reset()
        total, correct, lsum, nb = 0, 0, 0.0, 0
        for batch in it:
            x = batch.data[0]          # CSRNDArray
            y = batch.label[0]
            # bucket nnz so real LibSVM data (varying nnz/batch) reuses a
            # few compiled programs; zero-padded entries contribute nothing
            nnz = x._data.shape[0]
            pad = (-nnz) % 4096
            data = jnp.pad(x._data, (0, pad))
            indices = jnp.pad(x._aux["indices"], (0, pad))
            loss_d, correct_d, bgrad_d, contrib_d = step(
                weight._data, bias._data, data, indices,
                x._aux["indptr"], y._data)
            # THE one device fetch of the batch (everything above is
            # async dispatch; everything below is host-side numpy)
            loss, ncorrect, contrib, idx_host = jax.device_get(
                (loss_d, correct_d, contrib_d, indices))
            # unique over the REAL entries only: a padded index would put
            # a phantom zero-grad row in the row-sparse grad, and lazy
            # Adam's momentum would then drift that row on every batch
            uniq, inv = np.unique(idx_host[:nnz], return_inverse=True)
            vals = np.zeros((len(uniq), contrib.shape[1]), np.float32)
            np.add.at(vals, inv, contrib[:nnz])
            wgrad = RowSparseNDArray(jnp.asarray(vals),
                                     uniq.astype(np.int32),
                                     (x.shape[1], contrib.shape[1]))
            updater(0, wgrad, weight)
            bias_updater(1, mx.nd.from_jax(bgrad_d), bias)
            if kv is not None:
                kv.push("weight", weight)
                kv.row_sparse_pull("weight", out=weight,
                                   row_ids=x.indices)
            correct += int(ncorrect)
            total += batch_size
            lsum += float(loss)
            nb += 1
        loss_hist.append(lsum / nb)
    if measure:
        dt = time.perf_counter() - (t_start or time.perf_counter())
        rate = measured * it.num_data / dt if dt > 0 and measured else 0.0
        return correct / total, loss_hist, rate
    return correct / total, loss_hist


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--data", default=None, help="LibSVM file (default: "
                   "generate synthetic)")
    p.add_argument("--num-features", type=int, default=10000)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--kvstore", default=None, choices=[None, "local"])
    args = p.parse_args()

    path = args.data
    if path is None:
        path = os.path.join(tempfile.gettempdir(), "synthetic.libsvm")
        make_synthetic_libsvm(path, num_features=args.num_features)
    kv = mx.kv.create(args.kvstore) if args.kvstore else None
    acc, losses = train(path, args.num_features, args.batch_size,
                        args.epochs, kv=kv)
    print("final accuracy %.4f; loss %s" % (acc,
                                            ["%.4f" % v for v in losses]))


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
