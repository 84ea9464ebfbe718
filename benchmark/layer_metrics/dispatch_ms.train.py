"""Median host time of one call of the compiled step (the runner's clock
around each dispatch): what the frontend costs per step. Only calls made
while the profiler is off count: under it a call takes some ten times as
long, and that is the profiler's cost, not the frontend's."""
import statistics


def read(ctx):
    spans = ctx["window"].get("spans", {}).get("dispatch_s")
    return 1e3 * statistics.median(spans) if spans else None
