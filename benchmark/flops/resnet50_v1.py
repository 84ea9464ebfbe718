"""Operations the algorithm needs for one image, MAC = 2: every
convolution and the dense layer, counted from the configuration's shapes.
Training is forward + backward = 3 x forward (the backward pass makes two
products for each of the forward's); the optimizer's and batch norm's
elementwise work is not counted. For the published sizes this gives
8.18 GFLOP forward, the figure ``bench.py`` and PERF.md use."""
from benchmark.reference.resnet50_v1 import blocks


def forward_flops(cfg):
    size = cfg["image_size"]
    ch = cfg["channels"]
    hw = -(-size // 2)                       # stem, stride 2
    macs = hw * hw * 7 * 7 * cfg["image_channels"] * ch[0]
    hw = -(-hw // 2)                         # max-pool, stride 2
    for _s, _j, cin, c, stride, down in blocks(cfg):
        macs += hw * hw * cin * (c // 4)                 # 1x1 at input size
        out = -(-hw // stride)
        macs += out * out * 9 * (c // 4) * (c // 4)      # 3x3, strided
        macs += out * out * (c // 4) * c                 # 1x1
        if down:
            macs += out * out * cin * c                  # projection
        hw = out
    macs += ch[-1] * cfg["classes"]
    return 2 * macs


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)
