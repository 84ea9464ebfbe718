"""Median time the compiled step's module ran on the device, from the
trace: the module that took most device time in the traced stretch."""
import statistics


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["modules"]:
        return None
    runs = max(trace["modules"].values(), key=sum)
    return 1e3 * statistics.median(runs)
