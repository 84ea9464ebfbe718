"""Kimi Delta Attention's backward under its roofline: ``kda_bwd_flops`` /
``kda_bwd_bytes`` of the configuration's ``flops`` file (twice the
forward's operations; read q, k, v, the log-decay and the output's
cotangent, write four cotangents: the bytes bound it) over the device
seconds a call of every operation whose name starts ``kda_bwd``, summed
over the stages. A form that computes the chunk's forward again inside the
backward, or reads saved states, does more than that count."""
from benchmark import kernel_roofline


def read(ctx):
    return kernel_roofline.share(ctx, "kda_bwd", "kda_bwd_flops",
                                 "kda_bwd_bytes")
