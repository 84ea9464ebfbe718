"""What the readers of a kernel's share of the MXU's peak share. The harness hands a reader the
ten operations that took most device time in the traced stretch
(``ctx["trace"]["top_ops"]``: [name, seconds]) and every module's runs.
Each layer's call of a kernel is an operation of its own
(``flash_attention_fwd.<n>``) with the same shapes, so the mean over those
found among the ten, divided by the step's runs in the stretch, is the time
of one call. The kernel's operations a call come from the configuration's
``flops`` file; the peak from ``peaks.json``."""


def share(ctx, kernel, flops_fn):
    trace, peak = ctx["trace"], ctx["peak"]
    if not trace or not trace["modules"] or peak is None:
        return None
    fn = getattr(ctx["cell"].module("flops"), flops_fn, None)
    found = [s for name, s in trace["top_ops"]
             if name == kernel or name.startswith(kernel + ".")]
    runs = len(max(trace["modules"].values(), key=sum))
    if fn is None or not found or not runs:
        return None
    per_call = sum(found) / len(found) / runs
    return 100.0 * fn(ctx["cell"].cfg) / per_call / peak["bf16_flops_per_s"]
