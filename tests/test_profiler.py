"""Profiler tests (ref pattern: tests/python/unittest/test_profiler.py)."""
import json

import numpy as np

import mxtpu as mx
from mxtpu import profiler


def test_profiler_records_ops_and_dumps(tmp_path):
    fname = str(tmp_path / "trace.json")
    profiler.set_config(filename=fname)
    profiler.start()
    a = mx.nd.ones((32, 32))
    b = mx.nd.dot(a, a)
    (b + 1).asnumpy()
    profiler.stop()
    profiler.dump()
    with open(fname) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert any("dot" in n for n in names), names
    assert all(e["ph"] == "X" for e in trace["traceEvents"])
    stats = profiler.dumps()
    assert "Calls" in stats


def test_profiler_scopes(tmp_path):
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.start()
    with profiler.ProfileTask("mytask"):
        mx.nd.ones((4,)).asnumpy()
    profiler.stop()
    stats = profiler.dumps(reset=True)
    assert "mytask" in stats


def test_profiler_off_records_nothing(tmp_path):
    profiler.set_config(filename=str(tmp_path / "t2.json"))
    profiler.dumps(reset=True)
    mx.nd.ones((4,)).asnumpy()
    stats = profiler.dumps()
    assert "ones" not in stats


def test_xla_trace_bounded_and_idempotent(tmp_path):
    """A hung workload cannot leave a device capture running: the bounded
    watchdog stops it, and every later stop path is a no-op."""
    import glob
    import time

    d = str(tmp_path / "xla")
    profiler.set_config(filename=str(tmp_path / "t.json"), profile_xla=True,
                        xla_trace_dir=d, xla_trace_max_s=1.0)
    profiler.start()
    mx.nd.ones((8, 8)).asnumpy()
    # watchdog fires at 1s while the "workload" is stuck; poll rather than
    # fixed-sleep — under an oversubscribed host (parallel suite runs) the
    # timer thread can be scheduled well past its deadline
    deadline = time.time() + 20
    while profiler._PROF._xla_tracing and time.time() < deadline:
        time.sleep(0.25)
    assert not profiler._PROF._xla_tracing
    profiler.stop()          # second stop: must not raise
    profiler._stop_xla_trace()  # third: still a no-op
    assert glob.glob(d + "/**/*.xplane.pb", recursive=True)
    profiler.set_config(filename=str(tmp_path / "t.json"))  # reset config


def test_profiler_autostart_env(tmp_path):
    """MXTPU_PROFILER_AUTOSTART=1 profiles the whole program with no code
    changes and dumps profile.json at exit (ref env_var.md:152)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS",)}
    env.update({"PYTHONPATH": repo, "JAX_PLATFORMS": "cpu",
                "MXTPU_PROFILER_AUTOSTART": "1"})
    code = ("import mxtpu as mx\n"
            "mx.nd.dot(mx.nd.ones((4, 4)), mx.nd.ones((4, 4))).asnumpy()\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-1500:]
    trace = json.loads((tmp_path / "profile.json").read_text())
    assert any("dot" in e["name"] for e in trace["traceEvents"])
