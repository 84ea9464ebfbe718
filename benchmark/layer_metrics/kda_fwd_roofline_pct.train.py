"""Kimi Delta Attention's forward under its roofline: the least time a
call could take (``kda_fwd_flops`` / ``kda_fwd_bytes`` of the
configuration's ``flops`` file over the peaks: the recurrence's own ``7 K
V`` operations a token and head, and the bytes any form must move: q, k,
v, the float32 log-decay in, o out; the bytes bound it) over the device
seconds a call of every operation whose name starts ``kda_fwd``, summed
over the stages. A chunked form does more operations than that count (the
chunk's triangular factors and their inverse), so the share says how far
the form is from the operator's floor, not how busy the MXU is. Under
recomputation the forward runs twice a step; the mean a call stays a
call's."""
from benchmark import kernel_roofline


def read(ctx):
    return kernel_roofline.share(ctx, "kda_fwd", "kda_fwd_flops",
                                 "kda_fwd_bytes")
