"""Communication-bandwidth measurement kit (ref: tools/bandwidth/measure.py,
which times kvstore push+pull of a model's weight shapes across devices).

TPU-native re-design: the three links that matter on this runtime are
measured directly —

* host->device / device->host transfer (what the reference's kvstore
  pays per pull to CPU),
* on-mesh collective (jitted psum over the device mesh — the ICI path
  the compiled data-parallel step uses; needs >1 device: run with
  ``JAX_PLATFORMS=cpu`` under
  XLA_FLAGS=--xla_force_host_platform_device_count=8 for the virtual CPU
  mesh, or on a real multi-chip host),
* optional multi-process DCN allreduce (mxtpu.distributed host path) when
  a distributed runtime is initialized.

Timings sync by fetching one result element to the host, which waits for
the device on every backend (chip_smoke.py's ``sync`` phase checks it
against ``block_until_ready``).

Usage:
    python tools/bandwidth.py [--sizes-mb 1,4,16,64] [--model resnet50_v1]

With --model, the sweep uses that zoo model's actual parameter sizes
(the reference's default mode) aggregated into one blob per push.
Prints one line per (link, size): GB/s.
"""
import argparse
import time

import numpy as np


def _sync(x):
    np.asarray(__import__("jax").device_get(x.ravel()[:1]))


def _time(fn, reps=5):
    fn()  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def measure_transfer(nbytes, reps=5):
    """host->device and device->host GB/s for one f32 blob."""
    import jax

    n = max(nbytes // 4, 1)
    host = np.empty(n, np.float32)
    dev = jax.device_put(host)
    _sync(dev)

    def h2d():
        _sync(jax.device_put(host))

    def d2h():
        np.asarray(jax.device_get(dev))

    return nbytes / _time(h2d, reps) / 1e9, nbytes / _time(d2h, reps) / 1e9


def measure_collective(nbytes, reps=5):
    """Allreduce (psum) GB/s over all local devices; None with 1 device.

    The reference's convention is model_size / allreduce_time with every
    worker contributing the FULL model, so each device holds its own
    nbytes blob (a (ndev, n) array sharded on axis 0) and the psum
    reduces nbytes across the mesh."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        return None
    mesh = Mesh(np.array(devs), ("data",))
    n = max(nbytes // 4, 1)
    x = jax.device_put(np.ones((len(devs), n), np.float32),
                       NamedSharding(mesh, P("data", None)))

    @jax.jit
    def allreduce(v):
        return jax.shard_map(lambda s: jax.lax.psum(s, "data"), mesh=mesh,
                             in_specs=P("data", None),
                             out_specs=P("data", None))(v)

    def run():
        _sync(allreduce(x))

    return nbytes / _time(run, reps) / 1e9


def measure_dcn(nbytes, reps=3):  # noqa: D401
    """Multi-process host allreduce GB/s (mxtpu.distributed); None unless
    a distributed runtime is up (tools/launch.py -n workers)."""
    try:
        from mxtpu import distributed
        if not distributed.is_initialized():
            return None
    except Exception:
        return None
    blob = np.ones(max(nbytes // 4, 1), np.float32)

    def run():
        distributed.allreduce_host(blob)

    return nbytes / _time(run, reps) / 1e9


def model_param_bytes(name):
    """Total parameter bytes of a zoo model (the reference measures its
    kvstore on real model shapes, not synthetic blobs)."""
    import mxtpu as mx
    from mxtpu.gluon.model_zoo import vision

    net = vision.get_model(name)
    net.initialize()
    net(mx.nd.zeros((1, 3, 224, 224)))
    return sum(int(np.prod(p.data().shape)) * 4
               for p in net.collect_params().values())


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes-mb", default="1,4,16,64",
                    help="comma-separated blob sizes in MiB")
    ap.add_argument("--model", default=None,
                    help="zoo model whose total parameter size to sweep "
                         "(e.g. resnet50_v1), like the reference's default")
    ap.add_argument("--reps", type=int, default=5)
    ns = ap.parse_args()

    if ns.model:
        sizes = [model_param_bytes(ns.model)]
        print("%s parameters: %.1f MiB" % (ns.model, sizes[0] / 2**20))
    else:
        sizes = [int(float(s) * 2**20) for s in ns.sizes_mb.split(",")]

    print("%-10s %12s %12s %12s %12s" % ("size", "h2d GB/s", "d2h GB/s",
                                         "psum GB/s", "dcn GB/s"))
    for nbytes in sizes:
        h2d, d2h = measure_transfer(nbytes, ns.reps)
        coll = measure_collective(nbytes, ns.reps)
        dcn = measure_dcn(nbytes, ns.reps)
        print("%-10s %12.2f %12.2f %12s %12s"
              % ("%.0fMiB" % (nbytes / 2**20), h2d, d2h,
                 "%.2f" % coll if coll else "n/a (1 dev)",
                 "%.2f" % dcn if dcn else "n/a"))


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
