"""How often a step runs its attention and KDA kernels' forwards for each
of their backwards: the operations of the traced stretch whose names start
``flash_attention_fwd``, ``flash_window_fwd`` or ``kda_fwd`` (each layer's
call is an operation of its own, ``<kernel>.<n>``:
``benchmark/kernel_share.py``), counted, over those that start
``flash_attention_bwd``, ``flash_window_bwd`` or ``kda_bwd``. 1.0 where
every recomputed block keeps its kernels' outputs (or nothing is
recomputed), 2.0 where a block's second forward runs them again. What ran
on the device, not what was traced: a kept output whose kernel the compiler
did not drop still counts. A trace without such a backward has nothing to
read."""

KERNELS = ("flash_attention", "flash_window", "kda")


def read(ctx):
    ops = (ctx["trace"] or {}).get("ops", ())

    def count(direction):
        heads = tuple("%s_%s" % (k, direction) for k in KERNELS)
        return sum(name.split(".")[0] in heads for name in ops)

    backwards = count("bwd")
    return count("fwd") / backwards if backwards else None
