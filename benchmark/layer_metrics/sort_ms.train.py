"""The device milliseconds a step spends sorting: the seconds of every
operation of the traced stretch named ``sort`` or ``sort.<n>``, summed,
over the step's runs a chip in the stretch
(``kernel_share.seconds_a_call`` is the mean of those operations a run: times
their number). On
this chip ``lax.top_k`` is a full sort along the experts, so a router's
choice is one ``sort`` a routed layer and the sorted plan's ``argsort``
another; a recomputed block whose checkpoint keeps the choice and the order
sorts once a step, not twice. What ran on the device, not what was traced.
A trace without a sort or a module's run has nothing to read."""
from benchmark import kernel_share


def read(ctx):
    mean = kernel_share.seconds_a_call(ctx["trace"], "sort")
    if mean is None:
        return None
    return 1e3 * mean * sum(name.split(".")[0] == "sort"
                            for name in ctx["trace"]["ops"])
