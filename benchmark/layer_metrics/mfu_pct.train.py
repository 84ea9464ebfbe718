"""Model FLOP/s utilization: the operations forward + backward need per
sample (``benchmark/flops/<config>.py``, MAC = 2, nothing recomputed
counted) times the samples per second of this run, over chips times the
bf16 peak of ``benchmark/peaks.json``."""


def read(ctx):
    cell = ctx["cell"]
    rate = ctx["window"]["end_to_end"].get("train_samples_per_s")
    if rate is None or ctx["peak"] is None:
        return None
    flops = cell.module("flops").train_flops_per_sample(cell.cfg)
    return 100.0 * flops * rate / (cell.chips
                                   * ctx["peak"]["bf16_flops_per_s"])
