"""Smoke tests for the runnable examples (ref: example/image-classification,
example/gluon/word_language_model) — each must train end to end on tiny
synthetic shapes through its real __main__ path."""
import os
import runpy
import sys

import numpy as np

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(rel, argv):
    old = sys.argv
    sys.argv = ["x"] + argv
    try:
        runpy.run_path(os.path.join(ROOT, rel), run_name="__main__")
    finally:
        sys.argv = old


def test_image_classification_gluon(capsys):
    _run("examples/image_classification/train_cifar10.py",
         ["--epochs", "1", "--batch-size", "4", "--num-batches", "2",
          "--model", "resnet18_v1", "--dtype", "float32"])
    assert "epoch 0" in capsys.readouterr().out


def test_image_classification_module():
    _run("examples/image_classification/train_cifar10.py",
         ["--epochs", "1", "--batch-size", "4", "--num-batches", "2",
          "--module"])


def test_word_language_model(capsys):
    _run("examples/gluon/word_language_model.py",
         ["--epochs", "1", "--batch-size", "2", "--bptt", "4",
          "--vocab", "50", "--embed", "8", "--hidden", "8",
          "--corpus-len", "200", "--dtype", "float32"])
    assert "ppl" in capsys.readouterr().out


def test_lstm_bucketing_legacy_cells(capsys):
    """The classic mx.rnn + BucketingModule workflow (ref: example/rnn/
    bucketing/lstm_bucketing.py): legacy symbolic cells, one executor per
    bucket, must CONVERGE on the synthetic next-token pattern (uniform
    perplexity over vocab 32 would be 32; require < 10)."""
    _run("examples/rnn/lstm_bucketing.py",
         ["--epochs", "4", "--batch-size", "8", "--num-hidden", "16",
          "--num-embed", "8"])
    out = capsys.readouterr().out
    final = [l for l in out.splitlines() if l.startswith("final ")]
    assert final, out
    ppl = float(final[-1].split()[-1])
    assert ppl < 10.0, out


def test_sparse_linear_classification():
    # existing example (BASELINE config 5) keeps working through main
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "slc", os.path.join(ROOT, "examples/sparse/linear_classification.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    path = "/tmp/_ex_sparse.libsvm"
    m.make_synthetic_libsvm(path, num_rows=64, num_features=100,
                            nnz_per_row=5)
    result = m.train(path, 100, batch_size=16, epochs=2)
    acc = result[0]
    assert acc > 0.5


@pytest.mark.multidevice
def test_distributed_example_two_workers():
    """examples/distributed/train_dist.py through tools/launch.py -n 2:
    the symmetric multi-process path a reference dist_sync user follows
    (also guards the launcher's CPU pin for its workers)."""
    import signal
    import subprocess
    # own session so a timeout can kill the whole process GROUP — otherwise
    # hung grandchild workers outlive the test holding the coordinator port
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"), "-n", "2",
         sys.executable, os.path.join(ROOT, "examples", "distributed",
                                      "train_dist.py")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, err = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise
    r = subprocess.CompletedProcess(proc.args, proc.returncode, out, err)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "workers=2" in r.stdout
    assert "exported checkpoint" in r.stdout


def test_gluon_mnist_converges(capsys):
    """Canonical gluon MNIST MLP (ref: example/gluon/mnist.py) on the
    synthetic prototype set: must reach high val accuracy in 2 epochs."""
    _run("examples/gluon/mnist.py",
         ["--epochs", "2", "--batch-size", "50", "--hidden", "64",
          "--synthetic-size", "600"])
    out = capsys.readouterr().out
    assert "val-acc" in out
    acc = float(out.strip().splitlines()[-1].split()[-1])
    assert acc > 0.9, out


def test_gluon_dcgan_runs(capsys):
    """Adversarial two-trainer loop (ref: example/gluon/dcgan.py): both
    losses must stay finite through an epoch of alternating updates."""
    _run("examples/gluon/dcgan.py",
         ["--epochs", "1", "--batches-per-epoch", "3", "--batch-size", "4",
          "--ngf", "8", "--ndf", "8", "--nz", "8"])
    out = capsys.readouterr().out
    assert "lossD" in out
    toks = out.strip().splitlines()[-1].split()
    lossD, lossG = float(toks[3]), float(toks[5])
    assert np.isfinite(lossD) and np.isfinite(lossG), out


def test_numpy_ops_custom_softmax(capsys):
    """CustomOp escape hatch (ref: example/numpy-ops/custom_softmax.py):
    host-side NumPy fwd/bwd must match the built-in op and its grad."""
    _run("examples/numpy_ops/custom_softmax.py", [])
    assert "OK" in capsys.readouterr().out


@pytest.mark.multidevice
def test_model_parallel_tp_mlp(capsys):
    """Megatron-style column+row parallel MLP (ref: example/model-parallel,
    re-expressed as GSPMD rules) on the 8-device mesh: loss must fall."""
    _run("examples/model_parallel/tp_mlp.py",
         ["--steps", "8", "--batch-size", "16", "--hidden", "64"])
    out = capsys.readouterr().out
    first, last = out.strip().splitlines()[-1].split()[-3], \
        out.strip().splitlines()[-1].split()[-1]
    assert float(last) < float(first), out


def test_cnn_text_classification_converges(capsys):
    """Kim-2014 text CNN (ref: example/cnn_text_classification): parallel
    Conv1D widths + max-over-time pooling must crack the keyword task."""
    _run("examples/cnn_text_classification/text_cnn.py",
         ["--epochs", "3", "--train-size", "512"])
    out = capsys.readouterr().out
    acc = float(out.strip().splitlines()[-1].split()[-1])
    assert acc > 0.8, out


def test_multi_task_both_heads_learn(capsys):
    """Shared-trunk two-head training (ref: example/multi-task): summed
    losses must teach BOTH heads above chance by a wide margin."""
    _run("examples/multi_task/multitask_mlp.py",
         ["--epochs", "6", "--train-size", "1024"])
    out = capsys.readouterr().out
    toks = out.strip().splitlines()[-1].split()
    acc1, acc2 = float(toks[-3]), float(toks[-1])
    assert acc1 > 0.6 and acc2 > 0.8, out


def test_ssd_detection_trains_and_detects():
    """Tiny SSD over the MultiBox op family (ref example/ssd): loss
    falls, and inference decodes + NMS-es real detections."""
    import importlib.util
    import numpy as np
    spec = importlib.util.spec_from_file_location(
        "train_ssd", os.path.join(ROOT, "examples", "ssd", "train_ssd.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    net, anchors, hist = m.train(num_images=16, batch_size=8, epochs=6)
    assert hist[-1] < hist[0], hist
    imgs, labels = m.make_synthetic(2, seed=123)
    det = m.detect(net, anchors, imgs).asnumpy()
    assert det.ndim == 3 and det.shape[2] == 6
    kept = det[0][det[0][:, 0] >= 0]
    assert len(kept) > 0          # at least one post-NMS detection
    assert np.isfinite(kept).all()
    best = kept[np.argmax(kept[:, 1])]
    assert best[0] == 0           # the single foreground class
    assert 0.0 <= best[1] <= 1.0  # a probability score
    # the decoded box is a plausible region, not a degenerate point —
    # the short training run does not localize tightly, so assert
    # overlap with the image rather than IoU against labels
    gt = labels[0, 0, 1:]
    x0, y0, x1, y1 = best[2:6]
    assert x1 > x0 and y1 > y0
    assert x0 < gt[2] and x1 > gt[0]  # horizontal ranges intersect


def test_bi_lstm_sort_learns():
    """Bidirectional LSTM sorts integer sequences (ref
    example/bi-lstm-sort): per-token accuracy far above the 1/vocab
    chance level after a short hybridized training run."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "sort_lstm", os.path.join(ROOT, "examples", "bi_lstm_sort",
                                  "sort_lstm.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    net, hist = m.train(num=512, epochs=15)
    assert hist[-1] < hist[0] * 0.5, hist
    tok_acc, _ = m.accuracy(net, num=64)
    assert tok_acc > 0.4, tok_acc  # chance = 1/16
