"""Rehearsal of ``chip_smoke.py`` (the on-chip bring-up proof) on the CPU.

The script's whole program is the function ``chip_smoke.run(sizes, chips)``;
here it runs at ``TINY`` sizes in a child process (its own JAX, its own
compile cache directory handed in through ``JAX_COMPILATION_CACHE_DIR``), so
wrong paths, arguments and control flow are found without chip time. Nothing
here is a device result: the checks only a chip can meet are skipped inside
``run`` by what ``jax.devices()`` reports. The script itself must REFUSE the
CPU — that is the other half of the contract.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REHEARSE = ("import chip_smoke; "
             "print(chip_smoke.run(chip_smoke.TINY, chips=%d)['count'])")


def _child(argv, tmp_path, devices=1, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                        % devices)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla_cache")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("MXTPU_COMPILE_CACHE_DIR", None)
    return subprocess.run([sys.executable] + argv, env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def _phases(stdout):
    recs = [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]
    return {r["phase"]: r for r in recs}


def test_rehearsal_one_chip_phases(tmp_path):
    proc = _child(["-c", _REHEARSE % 1], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    phases = _phases(proc.stdout)
    assert list(phases) == ["device", "sync", "train_resnet50",
                            "train_bert_base", "flash_two_widths",
                            "flash_grouped", "window_attention",
                            "sparse_attention", "kda_attention",
                            "kda_conv", "routed_layer", "gluon_trainer",
                            "serve", "warm_start", "total"]
    assert max(phases["flash_two_widths"]["gaps"].values()) <= 2e-2
    assert max(phases["flash_grouped"]["gaps"].values()) <= 2e-2
    # off the chip the plain path runs, which repeats K and V and says so
    assert phases["flash_grouped"]["pallas_flash"]["grouped"] == 1
    assert phases["flash_grouped"]["pallas_flash"]["kv_repeated"] > 0
    # both calls of a window / global stack's attention; off the chip the
    # windowed one takes the plain path, which skips nothing, and says so
    for name, windowed in (("windowed", 1), ("global", 0)):
        call = phases["window_attention"][name]
        assert max(call["gaps"].values()) <= 2e-2
        assert call["pallas_flash"]["windowed"] == windowed
        assert call["pallas_flash"]["window_unskipped"] == windowed
    # one layer of sparse attention: exact sets, and off the chip the plain
    # path, which visits the whole square and says so
    sparse = phases["sparse_attention"]
    assert max(sparse["gaps"].values()) <= 2e-2
    assert sparse["sparse_attention"]["fallbacks"] == 1
    assert sparse["pairs"] == {"selected": 32 * 256 - 32 * 31 // 2,
                               "visited": 256 * 256}
    # one layer of Kimi Delta Attention against the recurrence; off the
    # chip the plain path, which says so
    linear = phases["kda_attention"]
    assert max(linear["gaps"].values()) <= 3e-2
    assert linear["kda_attention"] == {"calls": 1, "fallbacks": 1,
                                       "chunks": 6}
    # that layer's short filter, the value and both gradients of four
    # cases; off the chip the plain function, which says why
    filters = phases["kda_conv"]
    assert len(filters["gaps"]) == 12
    assert max(v for k, v in filters["gaps"].items() if "float32" in k) <= 2e-6
    assert max(filters["gaps"].values()) <= 4e-3
    assert filters["kda_conv"] == {"calls": 8, "pallas": 0, "xla": 8}
    assert filters["reasons"] == {"platform": 8}
    rows = phases["routed_layer"]["rows"]
    assert 0 < rows["live"] <= rows["run"] < rows["total"]
    assert max(phases["routed_layer"]["gaps"].values()) <= 3e-2
    # off the chip the grouped products are ragged_dot's, nine a rung, and
    # the phase says why (on the chip the kernel takes all of them)
    assert phases["routed_layer"]["grouped_products"] == {
        "pallas": 0, "xla": {"platform": 9 * len(
            phases["routed_layer"]["rungs"])}}
    for rec in phases.values():
        assert rec["seconds"] >= 0 and rec["compile_seconds"] >= 0
    assert phases["device"]["platform"] == "cpu"
    assert phases["serve"]["compiles_after_warmup"] == 0
    assert phases["warm_start"]["warm_compiles"] == 0
    assert phases["warm_start"]["disk_hits"] > 0
    assert phases["warm_start"]["bit_equal"] is True
    # the cache rule, end to end: the environment placed the XLA cache,
    # so the entries are THERE and the run says so
    assert phases["total"]["xla_cache_dir"] == str(tmp_path / "xla_cache")
    assert os.listdir(tmp_path / "xla_cache")


@pytest.mark.multidevice
def test_rehearsal_four_chip_phases(tmp_path):
    """``--chips 4`` on four virtual CPU devices: meshes, sharding rules
    and the disk-warm replica, which the driver never runs."""
    proc = _child(["-c", _REHEARSE % 4], tmp_path, devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.splitlines()[-1] == "4"
    phases = _phases(proc.stdout)
    assert list(phases) == ["device", "dp_resnet50", "dp_gluon_trainer",
                            "replicas", "total"]
    assert phases["dp_resnet50"]["param_device_span"] == 4
    assert phases["dp_resnet50"]["collectives"]
    assert phases["dp_gluon_trainer"]["param_device_span"] == 4
    assert len(set(phases["replicas"]["replica_devices"])) == 4
    assert phases["replicas"]["replacement_compiles"] == 0
    assert phases["replicas"]["replacement_disk_hits"] > 0


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_script_refuses_the_cpu(tmp_path, argv):
    """No accelerator -> non-zero, a message that names the platform it
    found, and NO result line."""
    proc = _child(["chip_smoke.py"] + argv, tmp_path, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "tpu" in proc.stderr
    assert '"ok"' not in proc.stdout
