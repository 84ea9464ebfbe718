"""Both flash kernels with keys and queries of one width and values of
another (latent attention: 192 = 128 + 64 rotary against 128), causal, over
several blocks, through the Pallas interpreter against the two oracles of
``tests/test_flash_attention.py``: the blockwise float32 backward on the
same residuals, and ``jax.vjp`` of the plain XLA attention."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")

_CASES = [
    pytest.param(d, dv, t, dtype, causal,
                 id="qk%d-v%d-t%d-%s-%s" % (d, dv, t, dtype,
                                            "causal" if causal else "full"))
    for d, dv in ((192, 128), (64, 128), (192, 64))
    for t in (256, 384)
    for dtype in ("float32", "bfloat16")
    for causal in (True, False)
]


def _gap(got, ref):
    got, ref = (np.asarray(x, np.float32) for x in (got, ref))
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def _operands(d, dv, t, dtype, seed=11):
    rng = np.random.RandomState(seed)
    dt = jnp.dtype(dtype)
    return (jnp.asarray(rng.randn(1, 2, t, d), dt),
            jnp.asarray(rng.randn(1, 2, t, d), dt),
            jnp.asarray(rng.randn(1, 2, t, dv), dt),
            jnp.asarray(rng.randn(1, 2, t, dv), dt))


@pytest.mark.parametrize("d,dv,t,dtype,causal", _CASES)
def test_forward_two_widths(monkeypatch, d, dv, t, dtype, causal):
    """Blocks of 128: two or three a side, so the online softmax carries
    its state over blocks and the causal skip is taken."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, _ = _operands(d, dv, t, dtype)
    fa.reset_dispatch_stats()
    out, lse = fa.flash_attention_with_lse(q, k, v, causal, None, 128, 128)
    assert fa.DISPATCH_STATS["pallas"] == 1 and fa.DISPATCH_STATS["xla"] == 0
    want, want_lse = fa._xla_attention_lse(q, k, v, fa.Mask(causal),
                                           d ** -0.5)
    assert out.shape == (1, 2, t, dv) and out.dtype == q.dtype
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert _gap(out, want) <= tol
    assert _gap(lse, want_lse) <= tol


@pytest.mark.parametrize("d,dv,t,dtype,causal", _CASES)
def test_backward_two_widths(monkeypatch, d, dv, t, dtype, causal):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g = _operands(d, dv, t, dtype)
    scale = d ** -0.5
    mask = fa.Mask(causal)
    out, lse = fa._xla_attention_lse(q, k, v, mask, scale)
    blocks, refused = fa._plan(q, k, v, mask, 128, 128, "backward")
    assert refused is None and blocks == (128, 128)
    got = fa._fa_backward_pallas(q, k, v, out, lse, g, mask, scale, *blocks)
    oracle = fa._fa_backward_blockwise(q, k, v, out, lse, g, mask, scale,
                                       128)
    _, vjp = jax.vjp(lambda q_, k_, v_: fa._xla_attention(
        q_, k_, v_, causal, scale), q, k, v)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for name, a, b, c, like in zip(("dq", "dk", "dv"), got, oracle, vjp(g),
                                   (q, k, v)):
        assert a.shape == like.shape and a.dtype == like.dtype, name
        assert _gap(a, b) <= tol, (name, "blockwise", _gap(a, b))
        assert _gap(a, c) <= tol, (name, "plain vjp", _gap(a, c))


@pytest.mark.parametrize("d,want", [(64, 128), (96, 128), (128, 128),
                                    (160, 256), (192, 192), (256, 256),
                                    (320, 320)])
def test_which_widths_are_padded(d, want):
    """Under 128 lanes: to 128. Above: only what does not fill whole
    half-tiles of 64."""
    x = jnp.zeros((1, 1, 8, d), jnp.bfloat16)
    (padded,) = fa._pad_head_dim(x)
    assert padded.shape[-1] == want
    assert (padded is x) == (want == d)


@pytest.mark.parametrize("n_q,n_k,bq,bk", [(2, 3, 128, 128), (16, 16, 512, 512),
                                          (3, 2, 128, 128), (2, 8, 256, 128),
                                          (1, 4, 200, 128)])
def test_causal_backward_names_only_blocks_that_exist(n_q, n_k, bq, bk):
    """With more keys than queries some k blocks are seen by no row: the
    q block they name must still lie inside the array (the interpreter
    would clamp a block index past the end; the chip halts on it). A step
    that computes names its own block."""
    for j in range(n_k):
        for i in range(n_q):
            got = int(fa.Mask(True).q_block(j, i, bq, bk, n_q))
            assert 0 <= got < n_q
            if j * bk <= i * bq + bq - 1:          # the kernel's ``run``
                assert got == i


@pytest.mark.parametrize("n_q,n_k,bq,bk", [
    (1, 1, 512, 512), (2, 2, 128, 128), (16, 16, 512, 512),    # equal
    (8, 8, 1024, 1024), (4, 8, 256, 128), (2, 8, 512, 128),    # 2:1, 4:1
    (8, 4, 128, 256), (8, 2, 128, 512),                        # 1:2, 1:4
    (2, 3, 128, 128), (3, 2, 128, 128), (1, 4, 200, 128),      # Tq != Tk
    (3, 5, 384, 128), (5, 3, 128, 384), (2, 8, 256, 128)])
def test_block_predicate_sorts_every_pair(n_q, n_k, bq, bk):
    """``Mask.block_case`` against the mask itself, pair by pair: a pair called
    visible has no masked position, a skipped pair no visible one, a
    crossed pair both. The forward's index map names ``j`` wherever the
    pair is not skipped, and only blocks inside the arrays (the chip halts
    on a block index past the end; the interpreter clamps it and says
    nothing); the ``pallas_flash.block_pairs`` counts are the brute-force
    ones."""
    want = {"skipped": 0, "visible": 0, "crossed": 0}
    mask = fa.Mask(True)
    for i in range(n_q):
        for j in range(n_k):
            sees = (np.arange(i * bq, (i + 1) * bq)[:, None]
                    >= np.arange(j * bk, (j + 1) * bk)[None, :])
            visible, crossed = mask.block_case(i, j, bq, bk)
            assert isinstance(visible, bool) and isinstance(crossed, bool)
            assert not (visible and crossed)
            assert visible == bool(sees.all()), (i, j)
            assert crossed == bool(sees.any() and not sees.all()), (i, j)
            kind = ("visible" if visible else
                    "crossed" if crossed else "skipped")
            want[kind] += 1
            named = int(mask.k_block(i, j, bq, bk))
            assert 0 <= named < n_k
            if kind != "skipped":
                assert named == j
            else:          # the last block the q block needed, not j
                assert named < j and mask.live(i, named, bq, bk)
    # the same answers on arrays, which is how a kernel's ids arrive
    visible, crossed = mask.block_case(
        jnp.arange(n_q)[:, None], jnp.arange(n_k)[None, :], bq, bk)
    assert int(visible.sum()) == want["visible"]
    assert int(crossed.sum()) == want["crossed"]
    for causal in (True, False):
        fa.reset_dispatch_stats()
        fa.Mask(causal).count_block_pairs(n_q, n_k, bq, bk)
        assert fa.DISPATCH_STATS["block_pairs"] == (want if causal else {
            "skipped": 0, "visible": n_q * n_k, "crossed": 0})


def test_block_pairs_of_the_two_cells():
    """What ``chip_smoke.py`` prints and PERF.md quotes: a head of the
    kanana cell at 512 x 512 has 120 / 120 / 16 pairs, a head of BERT's
    one visible pair."""
    fa.reset_dispatch_stats()
    fa.Mask(True).count_block_pairs(16, 16, 512, 512)
    assert fa.DISPATCH_STATS["block_pairs"] == {
        "skipped": 120, "visible": 120, "crossed": 16}
    fa.reset_dispatch_stats()
    fa.Mask().count_block_pairs(1, 1, 512, 512)
    assert fa.DISPATCH_STATS["block_pairs"] == {
        "skipped": 0, "visible": 1, "crossed": 0}


def test_grad_two_widths_runs_both_kernels(monkeypatch):
    """``jax.grad`` through the public function at 192 / 128: the forward
    and the backward both run as kernels, and no fallback is counted."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, _ = _operands(192, 128, 256, "float32")
    fa.reset_dispatch_stats()
    got = jax.grad(lambda *a: jnp.sum(
        fa.flash_attention(*a, True, None, 128, 128) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1)
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0
    ref = jax.grad(lambda *a: jnp.sum(
        fa._xla_attention(*a, True, 192 ** -0.5) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        assert _gap(a, b) <= 1e-5
