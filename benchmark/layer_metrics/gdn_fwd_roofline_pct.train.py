"""Gated DeltaNet's recurrence, forward, under its roofline: the least
time a call could take (``gdn_fwd_flops`` / ``gdn_fwd_bytes`` of the
configuration's ``flops`` file over the peaks: the recurrence's own ``7 K
V`` operations a token and value head, and the bytes any form must move: q
and k at the key heads, v in and o out at the value heads, one float32
decay and beta a head) over the device seconds a call of every operation
whose name starts ``gdn_fwd``, summed over the stages. A chunked form does
more operations than that count (the chunk's triangular factors and their
inverse), so the share says how far the form is from the operator's floor,
not how busy the MXU is. A program without such an operation (one from
before the kernels) has nothing to read."""
from benchmark import kernel_roofline


def read(ctx):
    return kernel_roofline.share(ctx, "gdn_fwd", "gdn_fwd_flops",
                                 "gdn_fwd_bytes")
