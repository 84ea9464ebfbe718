"""The flash forward kernel's operations a call (``flash_fwd_flops`` of the
configuration's ``flops`` file: q k^T and p v over the causal pairs, at the
published head widths) over its device time a call, as a share of the bf16
peak. The kernel computes more than that (padded widths, whole diagonal
blocks), so the share stays under what the MXU did."""
from benchmark import kernel_share


def read(ctx):
    return kernel_share.share(ctx, "flash_attention_fwd", "flash_fwd_flops")
