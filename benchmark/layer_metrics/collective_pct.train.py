"""Share of the device's busy seconds spent in collective operations: the
seconds of every operation of the traced stretch whose name starts with
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all`` or
``collective-permute`` (their asynchronous ``-start`` / ``-done`` halves
among them), over the busy seconds, both as means over the chips' planes.
A trace with no such operation (one chip) has nothing to read."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    found = [s for name, s in trace["ops"].items()
             if name.startswith(COLLECTIVES)]
    if not found or not trace["busy_s"]:
        return None
    return 100.0 * sum(found) / trace["busy_s"]
