"""Smoke test for the inference scoring benchmark (tools/benchmark_score.py,
analog of the reference's example/image-classification/benchmark_score.py):
it must import, resolve zoo models by the reference's dotted names, and
produce a finite img/s on CPU."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def test_score_model_smoke():
    from benchmark_score import score_model
    rate = score_model("squeezenet1.0", 2, steps=2, image_size=64)
    assert np.isfinite(rate) and rate > 0


def test_get_model_accepts_dotted_names():
    from mxtpu.gluon.model_zoo import vision
    net = vision.get_model("mobilenet1.0", classes=10)
    assert net is not None


def test_parse_log_table(tmp_path):
    """tools/parse_log.py parses this framework's (reference-format)
    training logs into a table (ref: tools/parse_log.py)."""
    import subprocess
    import sys

    log = tmp_path / "t.log"
    log.write_text(
        "INFO:root:Epoch[0] Batch [20]\tSpeed: 1000.00 samples/sec\t"
        "accuracy=0.1\n"
        "INFO:root:Epoch[0] Train-accuracy=0.25\n"
        "INFO:root:Epoch[0] Time cost=12.3\n"
        "INFO:root:Epoch[0] Validation-accuracy=0.31\n"
        "INFO:root:Epoch[1] Train-accuracy=0.5\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "parse_log.py"),
         str(log), "--format", "csv"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.strip().splitlines()
    assert lines[0] == "epoch,Train-accuracy,Validation-accuracy,speed,time"
    assert lines[1] == "0,0.25,0.31,1000,12.3"
    assert lines[2].startswith("1,0.5")


def test_diagnose_cpu_verdict():
    """tools/diagnose.py must reach a CPU-ONLY/HEALTHY verdict promptly
    on the hermetic CPU backend (ref: tools/diagnose.py)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "diagnose.py")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-500:]
    assert "VERDICT: CPU-ONLY" in out.stdout or \
        "VERDICT: HEALTHY" in out.stdout, out.stdout[-2000:]
