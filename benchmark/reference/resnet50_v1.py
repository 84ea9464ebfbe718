"""ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1, 50-layer) as the
program's Gluon zoo builds it under NHWC: 7x7/2 stem, 3x3/2 max-pool, four
stages of bottlenecks with the stride on the 3x3, batch norm after every
convolution, global average pool, one dense layer. Plain float32
``jax.numpy`` at ``highest`` matmul precision; leaves are listed in the
order the program's ``collect_params()`` gives them.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common

EPS, BN_MOMENTUM = 1e-5, 0.9
# The scale of each bottleneck's last batch norm in the seeded weights. Small,
# in the manner of zero-initialised residual branches (Goyal et al.,
# arXiv:1706.02677): with 0.3-0.5 a randomly initialised batch-norm ResNet
# is chaotic, and the bf16 program's first gradient lay 42% from the float32
# reference's, the fp8 control's 76% (PERF.md, Findings): nothing separates
# the two there.
GAMMA_LAST = (0.05, 0.15)


def _bn(name, c, last=False):
    return [(name + "_gamma", (c,), "float32", True, "uniform",
             GAMMA_LAST if last else (0.8, 1.2)),
            (name + "_beta", (c,), "float32", True, "normal", 0.1),
            (name + "_running_mean", (c,), "float32", False, "normal", 0.1),
            (name + "_running_var", (c,), "float32", False, "uniform",
             (0.5, 1.5))]


def _conv(name, k, cin, cout, dtype):
    return [(name + "_weight", (k, k, cin, cout), dtype, True, "normal",
             math.sqrt(2.0 / (k * k * cin)))]


def blocks(cfg):
    """(stage, index, in_channels, channels, stride, downsample) per
    bottleneck, in order."""
    ch = cfg["channels"]
    for s, n in enumerate(cfg["layers"]):
        for j in range(n):
            cin = ch[s] if j == 0 else ch[s + 1]
            yield s, j, cin, ch[s + 1], (2 if s > 0 and j == 0 else 1), \
                j == 0


def param_specs(cfg):
    dt = cfg["dtype"]
    specs = _conv("stem", 7, cfg["image_channels"], cfg["channels"][0], dt) \
        + _bn("stem_bn", cfg["channels"][0])
    for s, j, cin, c, _stride, down in blocks(cfg):
        p = "s%db%d" % (s + 1, j)
        specs += _conv(p + "_c1", 1, cin, c // 4, dt) + _bn(p + "_n1", c // 4)
        specs += _conv(p + "_c2", 3, c // 4, c // 4, dt) \
            + _bn(p + "_n2", c // 4)
        specs += _conv(p + "_c3", 1, c // 4, c, dt) \
            + _bn(p + "_n3", c, last=True)
        if down:
            specs += _conv(p + "_cd", 1, cin, c, dt) + _bn(p + "_nd", c)
    wide = cfg["channels"][-1]
    specs += [("fc_weight", (cfg["classes"], wide), dt, True, "normal", 0.01),
              ("fc_bias", (cfg["classes"],), dt, True, "normal", 0.01)]
    return specs


def sample_inputs(cfg, key, n):
    """``n`` seeded rows that all differ: images in [-1, 1) in the
    configuration's dtype, labels as the program's loss takes them."""
    kx, ky = jax.random.split(key)
    size = cfg["image_size"]
    x = jax.random.uniform(kx, (n, size, size, cfg["image_channels"]),
                           jnp.float32, -1.0, 1.0).astype(cfg["dtype"])
    y = jax.random.randint(ky, (n,), 0, cfg["classes"]).astype(jnp.float32)
    return x, y


def forward(cfg, params, x, precision="float32"):
    """Logits of a training pass, and the new running statistics by leaf
    index.
    Each bottleneck is rematerialised in the backward pass, so that float32
    activations of the timed batch fit beside nothing else on one chip."""
    product = common.product(precision)

    def conv(x, w, stride, pad):
        return product(lambda a, b: lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=common.HIGHEST))(x, w)

    def bn(x, g, b, rm, rv):
        """-> (normalised, [new running mean, new running var])"""
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
        new = [rm * BN_MOMENTUM + mean * (1 - BN_MOMENTUM),
               rv * BN_MOMENTUM + var * (1 - BN_MOMENTUM)]
        return (x - mean) * (lax.rsqrt(var + EPS) * g) + b, new

    def unit(x, p, stride, pad):
        """conv + batch norm over the five leaves ``p``"""
        return bn(conv(x, p[0], stride, pad), *p[1:5])

    def bottleneck(x, p, stride, down):
        y, s1 = unit(x, p[0:5], 1, 0)
        y, s2 = unit(jax.nn.relu(y), p[5:10], stride, 1)
        y, s3 = unit(jax.nn.relu(y), p[10:15], 1, 0)
        sd = []
        if down:
            x, sd = unit(x, p[15:20], stride, 0)
        return jax.nn.relu(y + x), s1 + s2 + s3 + sd

    aux = {}

    def note(at, stats):
        """``stats`` are the running statistics of the units whose leaves
        start at ``at``, ``at`` + 5, ..."""
        for k, v in enumerate(stats):
            aux[at + 5 * (k // 2) + 3 + k % 2] = v

    x, stats = unit(x.astype(jnp.float32), params[0:5], 2, 3)
    note(0, stats)
    x = lax.reduce_window(jax.nn.relu(x), -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    at = 5
    for _s, _j, _cin, _c, stride, down in blocks(cfg):
        n = 20 if down else 15
        x, stats = jax.checkpoint(bottleneck, static_argnums=(2, 3))(
            x, list(params[at:at + n]), stride, down)
        note(at, stats)
        at += n
    x = jnp.mean(x, (1, 2))
    w, b = params[at], params[at + 1]
    logits = product(lambda a, b: jnp.dot(
        a, b.T, precision=common.HIGHEST))(x, w) + b.astype(jnp.float32)
    return logits, aux


def forward_loss(cfg):
    def fn(params, x, y, precision):
        logits, aux = forward(cfg, params, x, precision)
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(
            logp, y.astype(jnp.int32)[:, None], -1)[:, 0]
        return -jnp.mean(picked), aux
    return fn
