"""The training runner: one compiled whole step with its state, driven
from the seed through its first steps in set-up (those are what the plain
reference follows), then handed, the same object, to the measured window,
where steps are dispatched back to back with a fetch of the loss every
``fetch_every`` steps, as a training loop that logs does.

Traffic parameters: ``batch``, ``pool`` (seeded batches made on the device
and cycled), ``fetch_every``, ``optimizer``, ``reference_steps``.
"""
import gc
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.models import common as model_common
from benchmark.reference import common as ref_common


def _devices(cell):
    """The cell's chips (a rehearsal takes what there is, up to as many)."""
    return jax.devices()[:cell.chips]


def _pool(cell, seed):
    """Every batch of the pool in one jitted call from the seed, each put
    out along its leading axis over the cell's devices."""
    ref, cfg, traffic = cell.module("reference"), cell.cfg, cell.traffic

    def make(key):
        return [ref.sample_inputs(cfg, k, traffic["batch"])
                for k in jax.random.split(key, traffic["pool"])]
    key = jax.random.fold_in(jax.random.PRNGKey(np.uint32(seed % 2 ** 32)), 1)
    return jax.jit(make, out_shardings=ref_common.by_batch(
        _devices(cell)))(key)


def setup(cell, seed):
    cfg, traffic = cell.cfg, cell.traffic
    ref, model = cell.module("reference"), cell.module("models")
    opt, n_ref = traffic["optimizer"], traffic["reference_steps"]
    specs = ref.param_specs(cfg)
    leaves = ref_common.init_params(specs, seed, _devices(cell))
    start = [jnp.copy(x) for x in leaves]
    net = model.build(cfg, specs, leaves)
    step = model.train_step(cfg, net, opt)
    pool = [model.batch(cfg, x, y) for x, y in _pool(cell, seed)]

    # the first steps, through the window's own call and feed
    losses, grad = [], None
    for t in range(n_ref):
        losses.append(step(*pool[t % len(pool)]))
        if t == 0:     # to the host: the next step donates these buffers
            grad = [ref_common.first_grad(opt, s) for s in
                    model_common.optimizer_state(step, specs)]
    after = model_common.trained_leaves(net)
    # where the program put its leaves (its mesh is its own business)
    delta = jax.jit(ref_common.delta_norms)(
        after, jax.device_put(start, [a.sharding for a in after]))
    first = {"losses": [float(x.asnumpy()) for x in losses],
             "grads": grad, "delta_norms": np.asarray(delta)}
    del start, leaves, after
    return {"cell": cell, "seed": seed, "step": step, "net": net,
            "pool": pool, "first": first, "steps_done": n_ref}


def window(state, seconds, tracer):
    step, pool = state["step"], state["pool"]
    every = state["cell"].traffic["fetch_every"]
    i = state["steps_done"]
    dispatch_s, traced_s, fetched, bad = [], [], [], 0
    done = 0
    t0 = time.perf_counter()
    while True:
        for _ in range(every):
            a = time.perf_counter()
            loss = step(*pool[i % len(pool)])
            # a call under the profiler pays the profiler: kept apart
            (traced_s if tracer is not None and tracer.running
             else dispatch_s).append(time.perf_counter() - a)
            i += 1
        value = float(loss.asnumpy())          # the sync
        now = time.perf_counter()
        done += every
        fetched.append(value)
        if not np.isfinite(value):
            bad += every
        if tracer is not None:
            tracer.tick(now - t0)
        if now - t0 >= seconds:
            break
    if tracer is not None:
        tracer.stop()
    # the device is drained whenever the profiler starts or stops, so a
    # traced run's rate leaves those seconds out; an untraced run has none
    elapsed = now - t0 - (tracer.overhead_s if tracer else 0.0)
    state["steps_done"] = i
    batch = state["cell"].traffic["batch"]
    rate = (done - bad) * batch / elapsed
    return {"attempted": done, "failed": bad,
            "end_to_end": {"train_samples_per_s": rate},
            "spans": {"dispatch_s": dispatch_s},
            "notes": {"steps": done, "elapsed_s": elapsed,
                      "dispatch_ms_median": 1e3 * float(
                          np.median(dispatch_s)),
                      "dispatch_ms_median_profiler_on": 1e3 * float(
                          np.median(traced_s)) if traced_s else None,
                      "step_ms": 1e3 * elapsed / done,
                      "items_per_s": rate * state["cell"].cfg.get(
                          "items_per_sample", 1),
                      "loss_first_fetch": fetched[0],
                      "loss_last_fetch": fetched[-1]}}


def check(state):
    """Frees the program, then lets the plain reference follow the same
    first steps on the same batches from the same seed."""
    cell, seed, first = state["cell"], state["seed"], state["first"]
    for k in ("step", "net", "pool"):
        state.pop(k)
    gc.collect()
    return compare(cell, seed, first, "float32")


def compare(cell, seed, first, precision, tag="run"):
    """``first`` (a program's, or the control's, first steps) against the
    reference at ``precision``: [(name, value, limit)]."""
    cfg, traffic = cell.cfg, cell.traffic
    ref = cell.module("reference")
    batches = _pool(cell, seed)[:traffic["reference_steps"]]
    want = reference_steps(cell, seed, batches, precision)
    # what a device held while the reference's backward ran: the compiler's
    # own count of that program, and the leaves kept outside it
    for key, value in sorted(want["bytes"].items()):
        print("note reference_bytes.%s = %r" % (key, value))
    lim = cell.limits
    names = [s[0] for s in ref.param_specs(cfg)]
    trainable = [s[0] for s in ref.param_specs(cfg) if s[3]]
    per_leaf, overall = ref_common.leaf_distances(first["grads"],
                                                  want["grads"])
    vectors = {
        "first_grad_distance": (per_leaf, trainable),
        "param_change_norm_gap": (ref_common.leaf_gaps(
            first["delta_norms"], want["delta_norms"]), names)}
    os.makedirs(cell.out_dir, exist_ok=True)
    with open(os.path.join(cell.out_dir,
                           "check_%s_%d.json" % (tag, seed)), "w") as f:
        json.dump({"seed": seed, "precision": precision,
                   "losses": [first["losses"], want["losses"]],
                   "first_grad_distance_overall": overall,
                   "reference_grad_norms": ref_common.host_norms(
                       want["grads"]).tolist(),
                   "reference_delta_norms": want["delta_norms"].tolist(),
                   "per_leaf": {k: dict(zip(n, v.tolist()))
                                for k, (v, n) in vectors.items()}}, f)
    for what, (gaps, leaf_names) in vectors.items():
        worst = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:3]
        print("%s: median leaf %.4g; worst leaves %s" % (
            what, np.median(gaps), ", ".join(
                "%s %.4g" % (leaf_names[i], gaps[i]) for i in worst)))
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(first["losses"], want["losses"]))
    return [("loss_rel_gap", loss_gap, lim["loss_rel_gap"]),
            ("first_grad_distance", overall, lim["first_grad_distance"]),
            ("first_grad_distance_worst_leaf", float(np.max(per_leaf)),
             lim["first_grad_distance_worst_leaf"]),
            ("param_change_norm_gap", float(np.max(
                vectors["param_change_norm_gap"][0])),
             lim["param_change_norm_gap"])]


def reference_steps(cell, seed, batches, precision):
    ref = cell.module("reference")
    return ref_common.train_reference(
        ref.forward_loss(cell.cfg), ref.param_specs(cell.cfg),
        cell.traffic["optimizer"], seed, batches, precision, _devices(cell))


def control(cell, seed, precision):
    """The reference at ``precision`` in the program's place."""
    batches = _pool(cell, seed)[:cell.traffic["reference_steps"]]
    return compare(cell, seed,
                   reference_steps(cell, seed, batches, precision), "float32",
                   tag="control")
