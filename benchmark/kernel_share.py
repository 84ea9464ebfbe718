"""What the readers of a kernel's share of the MXU's peak share. The harness
hands a reader the whole operation table of the traced stretch
(``ctx["trace"]["ops"]``: seconds by name, mean over the chips' planes) and
every module's runs. Each layer's call of a kernel is an operation of its
own (``flash_attention_fwd.<n>``) with the same shapes, so the mean over
every ``<kernel>`` / ``<kernel>.<n>`` in the table, divided by the step's
runs a chip in the stretch, is the time of one call, wherever the kernel
ranks among the operations. The kernel's operations a call come from the
configuration's ``flops`` file; the peak from ``peaks.json``."""


def seconds_a_call(trace, kernel):
    """Mean device seconds of one call of ``kernel`` on one chip, or None
    where the trace has no such operation or no module's run."""
    if not trace or not trace["modules"]:
        return None
    found = [s for name, s in trace["ops"].items()
             if name == kernel or name.startswith(kernel + ".")]
    runs = len(max(trace["modules"].values(), key=sum)) / trace["planes"]
    if not found or not runs:
        return None
    return sum(found) / len(found) / runs


def share(ctx, kernel, flops_fn):
    per_call = seconds_a_call(ctx["trace"], kernel)
    if per_call is None or ctx["peak"] is None:
        return None
    fn = getattr(ctx["cell"].module("flops"), flops_fn, None)
    if fn is None:
        return None
    return 100.0 * fn(ctx["cell"].cfg) / per_call / ctx["peak"][
        "bf16_flops_per_s"]
