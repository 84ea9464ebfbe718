"""Grouped heads (``H_q`` query heads over ``H_kv`` key/value heads, query
head ``j`` reading key/value head ``j // group``) through both flash
kernels in the Pallas interpreter, against plain ``jnp`` attention on K and
V repeated to the query heads and its ``jax.vjp``: out, lse, dq, dk, dv.
K, V and their gradients stay at ``H_kv`` heads everywhere: the compiled
text of a grouped call holds no array of K's or V's rows at the query
heads."""
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import telemetry
from mxtpu.base import MXNetError

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")


def _plain(q, k, v, causal):
    """Attention the plain way: K and V repeated to the query heads."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    if causal:
        seen = jnp.arange(q.shape[2])[:, None] >= jnp.arange(k.shape[2])[None]
        s = jnp.where(seen, s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v,
                      precision="highest"), lse


def _operands(hq, hk, tq, tk, d, dv, dtype, seed=5):
    rng = np.random.RandomState(seed)
    arr = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    ops = (arr(2, hq, tq, d), arr(2, hk, tk, d), arr(2, hk, tk, dv),
           arr(2, hq, tq, dv), arr(2, hq, tq))
    return tuple(x.astype(dtype) for x in ops[:4]) + ops[4:]


def _gap(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# (H_q, H_kv), (Tq, Tk), (d, dv)
_HEADS = ((8, 2), (4, 4), (4, 1))
_LENGTHS = ((256, 256), (256, 384), (384, 256))
_WIDTHS = ((64, 64), (192, 128))
_CASES = [
    pytest.param(hq, hk, tq, tk, d, dv, causal, dtype,
                 id="h%dkv%d-t%dx%d-qk%dv%d-%s-%s" % (
                     hq, hk, tq, tk, d, dv,
                     "causal" if causal else "full", dtype))
    for hq, hk in _HEADS for tq, tk in _LENGTHS for d, dv in _WIDTHS
    for causal in (True, False)
    for dtype in (("float32", "bfloat16") if (tq, tk) == (256, 256)
                  else ("float32",))
    # causal with more queries than keys leaves whole rows without a key
    if not (causal and tq > tk)
]


@pytest.mark.parametrize("hq,hk,tq,tk,d,dv,causal,dtype", _CASES)
def test_grouped_kernels_match_plain_attention(monkeypatch, hq, hk, tq, tk, d,
                                               dv, causal, dtype):
    """Blocks of 128, so the online softmax carries its state, the causal
    skip is taken, and dk / dv gather every query head of a group over
    several q blocks."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g, g_lse = _operands(hq, hk, tq, tk, d, dv, dtype)
    fa.reset_dispatch_stats()
    (out, lse), vjp = jax.vjp(
        lambda *a: fa.flash_attention_with_lse(*a, causal, None, 128, 128),
        q, k, v)
    dq, dk, dv_ = vjp((g, g_lse))
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1)
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0
    assert stats["grouped"] == (hq != hk) and stats["kv_repeated"] == 0
    assert dk.shape == k.shape and dv_.shape == v.shape
    assert dk.dtype == k.dtype and dq.dtype == q.dtype
    f32 = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    want, ref_vjp = jax.vjp(lambda *a: _plain(*a, causal), *f32(q, k, v))
    want = want + ref_vjp(f32(g, g_lse))
    tol = 2e-5 if dtype == "float32" else 2.5e-2
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"),
                          (out, lse, dq, dk, dv_), want):
        assert _gap(a, b) <= tol, name


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hk", [(8, 2), (4, 1), (4, 4)])
def test_blockwise_oracle_and_xla_path_take_groups(hq, hk, causal):
    """The refusal path (XLA attention) and the float32 oracle of the
    backward take grouped heads too: they repeat K and V, say so in
    ``pallas_flash.kv_repeated``, and return dk, dv at ``H_kv`` heads."""
    q, k, v, g, g_lse = _operands(hq, hk, 128, 256, 32, 48, "float32")
    fa.reset_dispatch_stats()
    scale = 32 ** -0.5
    mask = fa.Mask(causal)
    (out, lse), vjp = jax.vjp(
        lambda *a: fa._xla_attention_lse(*a, mask, scale), q, k, v)
    got = (out, lse) + vjp((g, g_lse))
    assert fa.DISPATCH_STATS["kv_repeated"] == (1 if hq != hk else 0)
    want, ref_vjp = jax.vjp(lambda *a: _plain(*a, causal), q, k, v)
    want = want + ref_vjp((g, g_lse))
    for a, b in zip(got, want):
        assert a.shape == b.shape and _gap(a, b) <= 2e-5
    dq, dk, dv = fa._fa_backward_blockwise(q, k, v, out, lse, g, mask,
                                           scale, 128, g_lse=g_lse)
    for a, b in zip((dq, dk, dv), want[2:]):
        assert a.shape == b.shape and _gap(a, b) <= 2e-5


def test_public_function_off_the_kernel_counts_the_repeat():
    """Off the chip without the interpreter the public function takes the
    XLA path: a grouped call is counted, and so is its repeat of K, V."""
    q, k, v, g, _ = _operands(4, 2, 64, 64, 16, 16, "float32")
    fa.reset_dispatch_stats()
    dk = jax.grad(lambda k_: jnp.sum(fa.flash_attention(q, k_, v, True) * g))(k)
    assert dk.shape == k.shape
    assert fa.DISPATCH_STATS["grouped"] == 1
    assert fa.DISPATCH_STATS["kv_repeated"] >= 1
    assert telemetry.value("pallas_flash.kv_repeated") >= 1


def test_heads_that_do_not_divide_are_refused():
    q, k, v, _, _ = _operands(4, 3, 64, 64, 16, 16, "float32")
    with pytest.raises(MXNetError, match="do not divide"):
        fa.flash_attention(q, k, v, True)


@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_kv_head_map_is_the_flattened_head_over_the_group(group):
    """Row ``b * H_q + j`` of the flattened queries reads row ``b * H_kv +
    j // group`` of the flattened keys: one integer division."""
    hk, batch = 3, 4
    fn = fa._kv_head_map(group)
    for b in range(batch):
        for j in range(hk * group):
            assert fn(b * hk * group + j) == b * hk + j // group
    if group == 1:
        marker = object()
        assert fn(marker) is marker       # the equal-heads map is untouched


def test_compiled_grouped_step_holds_no_kv_at_the_query_heads(monkeypatch):
    """Forward and backward of a grouped call, compiled (XLA:CPU around the
    interpreted kernels): Tk differs from Tq, so every array with Tk rows
    is K's, V's or a gradient of theirs, and none of them has the query
    heads' count (8, or batch x 8 = 16 flattened) in front of it."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g, _ = _operands(8, 2, 256, 384, 64, 64, "bfloat16")

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, False, None, 128, 128)
        return jnp.sum((out * g).astype(jnp.float32))

    fa.reset_dispatch_stats()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    assert fa.DISPATCH_STATS["kv_repeated"] == 0
    kv_rows = set(re.findall(r"\[((?:\d+,)*)384,(?:64|128)\]", text))
    assert kv_rows, "no array of K's or V's rows in the text"
    for lead in kv_rows:
        dims = [int(x) for x in lead.split(",") if x]
        assert 8 not in dims and 16 not in dims, lead
    # K and V are padded 64 -> 128 at their own 2 heads (4 rows flattened)
    assert re.search(r"bf16\[(2,2|4),384,128\]", text)


def test_backward_vmem_reckons_whole_heads_of_dk_dv(monkeypatch):
    """Grouped heads keep dk and dv of a whole key/value head in VMEM while
    its query heads pass: the reckoning grows by their rows, and the one
    block rule still answers for the cell's shape."""
    small = fa._bwd_vmem(1024, 1024, 8192, 128, 128, 2)
    whole = fa._bwd_vmem(1024, 1024, 8192, 128, 128, 2, 8192)
    assert whole - small == (8192 - 1024) * 256 * (4 + 2 * 2)
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 128), jnp.bfloat16)
    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    blocks, refused = fa._plan(q, kv, kv, fa.Mask(True), fa._BLOCK_Q,
                               fa._BLOCK_K, "backward")
    assert refused is None and blocks == (1024, 1024)
