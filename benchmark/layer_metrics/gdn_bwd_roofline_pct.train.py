"""Gated DeltaNet's recurrence, backward, under its roofline:
``gdn_bwd_flops`` / ``gdn_bwd_bytes`` of the configuration's ``flops`` file
(twice the forward's operations; read q, k, v, the decay, beta and the
output's cotangent, write five cotangents, q's and k's at the key heads)
over the device seconds a call of every operation whose name starts
``gdn_bwd``, summed over the stages. A form that computes the chunk's
forward again inside the backward, reads saved states, or writes a key
head's cotangents a value head each, does more than that count. A program
without such an operation has nothing to read."""
from benchmark import kernel_roofline


def read(ctx):
    return kernel_roofline.share(ctx, "gdn_bwd", "gdn_bwd_flops",
                                 "gdn_bwd_bytes")
