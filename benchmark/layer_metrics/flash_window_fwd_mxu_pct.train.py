"""The windowed flash forward kernel's operations a call
(``flash_window_fwd_flops`` of the configuration's ``flops`` file: q k^T
and p v over the WINDOW'S pairs of (query, visible key), at the published
head width) over its device time a call (the program names a call with a
window ``flash_window_fwd``), as a share of the bf16 peak. A kernel that
visits pairs left of the window reads low. A program without such a
kernel, or a configuration without that count, has nothing to read."""
from benchmark import kernel_share


def read(ctx):
    return kernel_share.share(ctx, "flash_window_fwd",
                              "flash_window_fwd_flops")
