"""(query, key) pairs the traced sparse calls visit over the pairs their
sets hold (the program's ``sparse_attention.pairs_visited`` /
``.pairs_selected`` counters, at trace time, from the shapes: a head's
pairs of every call traced). 1.0 is the floor, a form that touches the
selected pairs alone; the masked form, which visits every causal block
pair, reads 4.5 at 2,048 keys of 16,384 under blocks of 1,024. A program
that traced no sparse call, or has no such counters, has nothing to
read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    selected = telemetry.value("sparse_attention.pairs_selected")
    if not selected:
        return None
    return telemetry.value("sparse_attention.pairs_visited") / selected
