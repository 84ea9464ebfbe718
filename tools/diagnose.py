"""Environment + accelerator diagnostics (ref: tools/diagnose.py, which
dumps platform/version/connectivity info for bug reports).

Prints versions, the relevant environment, the native library's build
state and a backend probe: device discovery, a first jitted dispatch
(compile included) and a warm dispatch, each timed. The probe runs in THIS
process — a chip belongs to one process at a time, so a diagnostic that
took it in a child would have to give it back before anything else ran.

Usage:
    python tools/diagnose.py

Verdicts: HEALTHY (an accelerator answered), CPU-ONLY (no accelerator
platform), BROKEN (import or backend start-up failed; the error is printed).
"""
import argparse
import os
import platform
import sys
import time

_TOOLS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TOOLS)
for _p in (_REPO, _TOOLS):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def section(title):
    print("\n----- %s -----" % title)


def versions():
    section("versions")
    print("python   :", sys.version.split()[0], platform.platform())
    for mod in ("jax", "jaxlib", "numpy", "flax", "optax", "orbax"):
        try:
            m = __import__(mod)
            print("%-9s: %s" % (mod, getattr(m, "__version__", "?")))
        except Exception as e:  # noqa: BLE001
            print("%-9s: unavailable (%s)" % (mod, e))
    try:
        import mxtpu
        print("mxtpu    :", getattr(mxtpu, "__version__", "dev"),
              os.path.dirname(mxtpu.__file__))
    except Exception as e:  # noqa: BLE001
        print("mxtpu    : IMPORT FAILED (%s)" % e)


def environment():
    section("environment")
    for k in sorted(os.environ):
        if k.startswith(("MXTPU_", "MXNET_", "JAX_", "XLA_", "LIBTPU_",
                         "PALLAS_", "TPU_")):
            v = os.environ[k]
            if any(t in k.upper() for t in ("TOKEN", "SECRET", "KEY")):
                v = "<redacted>"
            print("%s=%s" % (k, v))


def native_lib():
    section("native library")
    try:
        from mxtpu._native import build_error, get_lib
        lib = get_lib()
        print("_libmxtpu.so:", "loaded" if lib else
              "build failed: %s" % build_error())
    except Exception as e:  # noqa: BLE001
        print("_libmxtpu.so: unavailable (%s)" % e)


def backend_probe():
    """Device discovery + one cold and one warm dispatch, in-process;
    returns the verdict string."""
    section("backend probe")
    t0 = time.time()
    try:
        import jax
        import jax.numpy as jnp
        devs = jax.devices()
        print("devices (%.1fs): %s" % (time.time() - t0, devs))
        f = jax.jit(lambda x: x + 1)
        t1 = time.time()
        f(jnp.zeros((8,))).block_until_ready()
        print("first dispatch, compile included: %.3fs" % (time.time() - t1))
        t1 = time.time()
        f(jnp.zeros((8,))).block_until_ready()
        print("warm dispatch: %.3f ms" % ((time.time() - t1) * 1e3))
    except Exception as e:  # noqa: BLE001 — a diagnostic reports, never dies
        print("%s: %s" % (type(e).__name__, e))
        print("VERDICT: BROKEN — backend failed to start (%.1fs)"
              % (time.time() - t0))
        return "BROKEN"
    verdict = "CPU-ONLY" if devs[0].platform == "cpu" else "HEALTHY"
    print("VERDICT: %s (platform %s, kind %s, %d device(s), %.1fs total)"
          % (verdict, devs[0].platform, devs[0].device_kind, len(devs),
             time.time() - t0))
    return verdict


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)
    versions()
    environment()
    native_lib()
    verdict = backend_probe()
    return 0 if verdict in ("HEALTHY", "CPU-ONLY") else 1


if __name__ == "__main__":
    sys.exit(main())
