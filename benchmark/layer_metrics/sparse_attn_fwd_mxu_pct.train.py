"""The sparse forward kernel's operations a call (``sparse_attn_fwd_flops``
of the configuration's ``flops`` file: q k^T and p v over the SELECTED
pairs of (query, key) only, ``sum_t min(t + 1, topk)`` a sequence, at the
published head width, whatever form the kernel has) over its device time a
call (the program names the kernel ``sparse_attention_fwd``), as a share of
the bf16 peak. A kernel that visits every causal pair and masks the rest
reads at most ``selected / causal`` of what a causal kernel reads. A
program without such a kernel, or a configuration without that count, has
nothing to read."""
from benchmark import kernel_share


def read(ctx):
    return kernel_share.share(ctx, "sparse_attention_fwd",
                              "sparse_attn_fwd_flops")
