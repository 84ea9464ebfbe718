"""Shared performance model: datasheet peak tables, the ONE MFU
convention, and version-proof accessors over XLA's cost/memory analyses.

Before this module the chip peak-FLOPs table and the MFU convention lived
twice (``bench.py`` and ``tools/perf_peak.py``) and every consumer of
``Compiled.cost_analysis()`` read it its own way (``parallel/train.py``,
``tools/perf_bisect.py``). This module is the single copy both the offline benches and the runtime
observatory (:mod:`mxtpu.xprof`) draw from.

**The MFU convention** (one convention, everywhere): model FLOPs counted
MAC=2 (a multiply-accumulate is 2 FLOPs — the standard convention, and
how XLA counts), divided by the *datasheet* chip peak for the compute
dtype. ``hfu`` uses XLA's executed-FLOP count against the same peak.
Rounds 1-3 of PERF.md mixed MAC=1 counts with MAC=2 peaks and understated
utilization 2x — routing every denominator through :func:`peak_flops`
makes that class of bug structural.

Import-light by design: no jax import at module load (the accessors take
already-materialized analysis objects), so ``tools/telemetry_report.py``
can use the tables offline.
"""
from __future__ import annotations

import os

__all__ = ["NOMINAL_PEAK_TFLOPS", "HBM_BANDWIDTH_GBPS",
           "nominal_tflops", "peak_flops", "peak_bandwidth",
           "critical_intensity", "mfu", "cost_dict", "flops_of",
           "bytes_accessed_of", "memory_dict", "roofline_verdict"]

# Datasheet dense bf16 peak per chip, TFLOP/s, matched by substring
# against ``device.device_kind`` (PJRT kinds look like "TPU v5 lite",
# "TPU v4", ...). MAC=2 convention — the number printed on the datasheet.
# The v5e this repo is brought up on reports device_kind "TPU v5 lite"
# (chip_smoke.py ``device`` phase). A TPU kind that matches no row is an
# error (:func:`_lookup`), never a default.
NOMINAL_PEAK_TFLOPS = {
    "v5 lite": 197.0,   # v5e PJRT device_kind spelling
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,   # v6e (Trillium)
    "v6e": 918.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 46.0,
}

# Datasheet HBM bandwidth per chip, GB/s — the roofline's other axis.
HBM_BANDWIDTH_GBPS = {
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
    "v4": 1228.0,
    "v3": 900.0,
    "v2": 700.0,
}


def _device_kind(device):
    """(platform, kind) of ``device`` (an int index, a jax Device, or
    None = device 0). Returns ("unknown", "") when no backend answers."""
    if isinstance(device, str):
        return ("tpu", device.lower())  # offline: caller names the kind
    try:
        import jax
        if device is None or isinstance(device, int):
            device = jax.devices()[device or 0]
        return (device.platform,
                str(getattr(device, "device_kind", "")).lower())
    except Exception:  # noqa: BLE001 — no backend / dead PJRT client
        return ("unknown", "")


def _lookup(table, kind):
    for sub, v in table.items():
        if sub in kind:
            return v
    raise LookupError(
        "TPU device_kind %r is in no peak table of mxtpu/perf_model.py: "
        "add its datasheet row (a utilization against another chip's "
        "peak would be a wrong number, not an estimate)" % kind)


def nominal_tflops(device=None):
    """Datasheet peak TFLOP/s for ``device`` (bf16 dense, MAC=2), or None
    off-TPU. ``device`` may be a jax Device, an int index, a device-kind
    string (offline use), or None (device 0)."""
    platform, kind = _device_kind(device)
    if platform != "tpu":
        return None
    return _lookup(NOMINAL_PEAK_TFLOPS, kind)


def peak_flops(device=None):
    """Chip peak FLOP/s for the MFU denominator — ``MXTPU_PEAK_TFLOPS``
    override first (how a CPU-tier test or an unlisted chip pins the
    denominator), else the datasheet table. None when MFU is meaningless
    (CPU fallback, no override)."""
    env = os.environ.get("MXTPU_PEAK_TFLOPS")
    if env:
        try:
            return float(env) * 1e12
        except ValueError:
            pass
    t = nominal_tflops(device)
    return t * 1e12 if t else None


def peak_bandwidth(device=None):
    """Datasheet HBM bandwidth in bytes/s (``MXTPU_PEAK_GBPS`` override),
    or None off-TPU."""
    env = os.environ.get("MXTPU_PEAK_GBPS")
    if env:
        try:
            return float(env) * 1e9
        except ValueError:
            pass
    platform, kind = _device_kind(device)
    if platform != "tpu" and not env:
        return None
    return _lookup(HBM_BANDWIDTH_GBPS, kind) * 1e9


def critical_intensity(device=None):
    """The roofline ridge point, FLOPs/byte: executables whose arithmetic
    intensity sits below it are memory-bound on this chip (the fusion-gap
    methodology of arXiv:2301.13062 — the standing hand-kernel shortlist
    is exactly the memory-bound entries with the most FLOPs)."""
    pf, bw = peak_flops(device), peak_bandwidth(device)
    if not pf or not bw:
        return None
    return pf / bw


def mfu(flops_per_s, device=None, n_devices=1):
    """Achieved FLOP/s as a fraction of the datasheet peak across
    ``n_devices`` chips. None when the peak is unknown."""
    pf = peak_flops(device)
    if not pf or not flops_per_s:
        return None
    return float(flops_per_s) / (pf * max(int(n_devices), 1))


# ------------------------------------------------ XLA analysis accessors
def cost_dict(cost):
    """``Compiled.cost_analysis()`` as a plain dict ({} when the backend
    gives none) — THE accessor every consumer routes through."""
    return dict(cost) if cost else {}


def flops_of(compiled):
    """XLA cost-model FLOPs of a compiled executable, or None when the
    backend exposes none (some report -1 for "unknown" — treated as
    absent, never as a negative MFU)."""
    c = cost_dict(compiled.cost_analysis())
    v = c.get("flops")
    if v is None or float(v) <= 0:
        return None
    return float(v)


def bytes_accessed_of(compiled):
    """XLA cost-model bytes accessed (HBM traffic estimate), or None."""
    c = cost_dict(compiled.cost_analysis())
    v = c.get("bytes accessed")
    if v is None or float(v) <= 0:
        return None
    return float(v)


_MEM_FIELDS = {
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "temp_bytes": "temp_size_in_bytes",
    "generated_code_bytes": "generated_code_size_in_bytes",
    # alias = donated input buffers reused for outputs: the bytes the
    # donation discipline saves vs a copy-in/copy-out executable
    "donated_bytes": "alias_size_in_bytes",
}


def memory_dict(mem_stats):
    """``Compiled.memory_analysis()`` (a CompiledMemoryStats) as a plain
    int dict with stable keys; {} when the backend returns None."""
    if mem_stats is None:
        return {}
    out = {}
    for key, attr in _MEM_FIELDS.items():
        v = getattr(mem_stats, attr, None)
        if v is None and isinstance(mem_stats, dict):
            v = mem_stats.get(attr)
        if v is not None:
            out[key] = int(v)
    return out


def roofline_verdict(flops, bytes_accessed, ridge):
    """"compute"- vs "memory"-bound call for one executable given its
    cost-model arithmetic intensity and the chip ridge point; None when
    either side is unknown."""
    if not flops or not bytes_accessed or not ridge:
        return None
    return "memory" if (flops / bytes_accessed) < ridge else "compute"
