"""What the readers of an operator's share of its roofline share. The
operator may run as one kernel or as a few stages, each an operation whose
name starts with the operator's prefix (``kda_fwd``, or ``kda_fwd_wy`` and
``kda_fwd_state``): the seconds a call of every stage
(``kernel_share.seconds_a_call``) are summed, and the operator's roofline a
call, the larger of its operations over the bf16 peak and its bytes over
the memory's, both from the configuration's ``flops`` file and
``peaks.json``, is divided by that sum. A program without such an
operation, or a configuration without those counts, has nothing to
read."""
from benchmark import kernel_share


def share(ctx, prefix, flops_fn, bytes_fn):
    trace, peak = ctx["trace"], ctx["peak"]
    if not trace or peak is None:
        return None
    counts = ctx["cell"].module("flops")
    flops, moved = (getattr(counts, fn, None) for fn in (flops_fn, bytes_fn))
    if flops is None or moved is None:
        return None
    stages = sorted({name.split(".")[0] for name in trace["ops"]
                     if name.startswith(prefix)})
    seconds = [kernel_share.seconds_a_call(trace, s) for s in stages]
    if not seconds or None in seconds:
        return None
    least = max(flops(ctx["cell"].cfg) / peak["bf16_flops_per_s"],
                moved(ctx["cell"].cfg) / peak["hbm_bytes_per_s"])
    return 100.0 * least / sum(seconds)
