"""The sparse backward kernel's operations a call (``sparse_attn_bwd_flops``
of the configuration's ``flops`` file: its five products over the SELECTED
pairs only, at the published head width) over its device time a call (the
program names the kernel ``sparse_attention_bwd``), as a share of the bf16
peak; reads as ``sparse_attn_fwd_mxu_pct.train`` does. A program without
such a kernel, or a configuration without that count, has nothing to
read."""
from benchmark import kernel_share


def read(ctx):
    return kernel_share.share(ctx, "sparse_attention_bwd",
                              "sparse_attn_bwd_flops")
