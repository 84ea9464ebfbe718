"""A sliding window beside the causal mask (key ``j`` visible to query
``i`` iff ``i - window < j <= i``) through both flash kernels in the Pallas
interpreter, the XLA path and the blockwise oracle, against a plain
position-wise attention and its ``jax.vjp``: out, dq, dk, dv. The block
predicate's counts against a closed form; ``window >= T`` is the causal
call bit for bit; ``window`` without ``causal`` is refused."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.base import MXNetError

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")


def _plain(q, k, v, window):
    """Position by position, K and V repeated to the query heads."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(k.shape[2])[None]
    seen = (j <= i) & (j > i - window) if window else j <= i
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _operands(hq, hk, t, d, dtype="float32", seed=7):
    rng = np.random.RandomState(seed)
    arr = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32).astype(dtype)
    return arr(1, hq, t, d), arr(1, hk, t, d), arr(1, hk, t, d), \
        arr(1, hq, t, d)


def _gap(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _want(q, k, v, g, window):
    f32 = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    out, vjp = jax.vjp(lambda *a: _plain(*a, window), *f32(q, k, v))
    return (out,) + vjp(*f32(g))


# blocks of 128 asked for (384 takes 128s of an asked 256: the block does
# not divide it): a window smaller than a block, one block, several blocks
# and not a multiple, and one that masks nothing
_WINDOWS = (40, 128, 300, 512)
_CASES = [pytest.param(hq, hk, t, ask, window,
                       id="h%dkv%d-t%d-ask%d-w%d" % (hq, hk, t, ask, window))
          for hq, hk in ((2, 2), (4, 1), (7, 1))
          for t, ask in ((512, 128), (384, 256))
          for window in _WINDOWS]


@pytest.mark.parametrize("hq,hk,t,ask,window", _CASES)
def test_windowed_kernels_match_plain_attention(monkeypatch, hq, hk, t, ask,
                                                window):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g = _operands(hq, hk, t, 128)
    fa.reset_dispatch_stats()
    out, vjp = jax.vjp(
        lambda *a: fa.flash_attention(*a, True, None, ask, ask, window),
        q, k, v)
    got = (out,) + vjp(g)
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1)
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0
    assert stats["kv_repeated"] == 0 and stats["window_unskipped"] == 0
    assert stats["windowed"] == (1 if window < t else 0)
    want = _want(q, k, v, g, window)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and _gap(a, b) <= 2e-5, name


@pytest.mark.parametrize("window", [40, 200, 300])
@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128), (512, 128)])
@pytest.mark.parametrize("hq,hk", [(2, 2), (7, 1)])
def test_windowed_kernels_with_unequal_blocks(monkeypatch, hq, hk, bq, bk,
                                              window):
    """The backward of the cell's own shape halves its q block (dq of a
    head and dk, dv of a whole key/value head wait in VMEM): blocks of 512
    x 1,024 there, unequal ones here, a window narrower than a block and
    one that is no multiple of either."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g = _operands(hq, hk, 512, 128)
    fa.reset_dispatch_stats()
    out, vjp = jax.vjp(
        lambda *a: fa.flash_attention(*a, True, None, bq, bk, window), q, k, v)
    got = (out,) + vjp(g)
    assert fa.DISPATCH_STATS["bwd_pallas"] == 1
    assert fa._plan(q, k, v, fa.Mask(True, window), bq, bk, "backward") == (
        (bq, bk), None)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got,
                          _want(q, k, v, g, window)):
        assert a.shape == b.shape and _gap(a, b) <= 2e-5, name


@pytest.mark.parametrize("hq,hk", [(4, 4), (7, 1)])
def test_windowed_kernels_in_bfloat16(monkeypatch, hq, hk):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g = _operands(hq, hk, 512, 128, "bfloat16")
    out, vjp = jax.vjp(
        lambda *a: fa.flash_attention(*a, True, None, 128, 128, 300), q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out,) + vjp(g),
                          _want(q, k, v, g, 300)):
        assert a.dtype == jnp.bfloat16 and _gap(a, b) <= 2.5e-2, name


@pytest.mark.parametrize("window", _WINDOWS)
@pytest.mark.parametrize("hq,hk", [(2, 2), (4, 1), (7, 1)])
def test_xla_path_and_blockwise_oracle_take_the_window(hq, hk, window):
    """The plain paths keep their own position-wise masks, and a windowed
    call that takes one says so (``pallas_flash.window_unskipped``): they
    visit the pairs left of the window. 320 positions: off the lanes'
    granule, so the public function has no kernel for them."""
    t, scale = 320, 32 ** -0.5
    q, k, v, g = _operands(hq, hk, t, 32)
    want = _want(q, k, v, g, window)
    mask = fa.Mask(True, window)
    (out, lse), vjp = jax.vjp(
        lambda *a: fa._xla_attention_lse(*a, mask, scale), q, k, v)
    got = (out,) + vjp((g, jnp.zeros_like(lse)))
    oracle = fa._fa_backward_blockwise(q, k, v, out, lse, g, mask, scale, 64)
    for name, a, b, c in zip(("out", "dq", "dk", "dv"), got, want,
                             (out,) + oracle):
        assert _gap(a, b) <= 2e-5 and _gap(c, b) <= 2e-5, name
    fa.reset_dispatch_stats()
    public = jax.vjp(lambda *a: fa.flash_attention(*a, True, window=window),
                     q, k, v)
    for a, b in zip((public[0],) + public[1](g), want):
        assert _gap(a, b) <= 2e-5
    masks = window < t
    assert fa.DISPATCH_STATS["windowed"] == (1 if masks else 0)
    assert fa.DISPATCH_STATS["window_unskipped"] == (1 if masks else 0)


@pytest.mark.parametrize("window", [512, 513, 10 ** 6])
def test_a_window_that_masks_nothing_is_the_causal_call(monkeypatch, window):
    """Bit for bit, forward and backward, and under the causal kernels'
    own names: no windowed call is counted."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g = _operands(4, 2, 512, 128, "bfloat16")

    def run(*extra):
        out, vjp = jax.vjp(lambda *a: fa.flash_attention(
            *a, True, None, 128, 128, *extra), q, k, v)
        return (out,) + vjp(g)

    fa.reset_dispatch_stats()
    for a, b in zip(run(window), run()):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert fa.DISPATCH_STATS["windowed"] == 0
    text = jax.jit(lambda *a: fa.flash_attention(
        *a, True, None, 128, 128, window)).lower(q, k, v).as_text()
    assert "flash_window" not in text


def test_a_windowed_call_names_its_own_kernels(monkeypatch):
    """``flash_window_fwd`` / ``flash_window_bwd``: a trace tells the two
    kinds of call apart, and the readers of ``flash_attention_fwd`` /
    ``_bwd`` keep reading calls of one shape."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g = _operands(2, 1, 256, 128)

    def text(window):
        return str(jax.make_jaxpr(lambda *a: jax.vjp(
            lambda *b: fa.flash_attention(*b, True, None, 128, 128, window),
            *a)[1](g))(q, k, v))

    windowed, causal = text(128), text(0)
    for kind in ("fwd", "bwd"):
        assert "flash_window_" + kind in windowed
        assert "flash_attention_" + kind not in windowed
        assert "flash_attention_" + kind in causal
        assert "flash_window_" + kind not in causal


@pytest.mark.parametrize("q,k,causal,window,why", [
    ((1, 2, 128, 16), (1, 2, 128, 16), False, 32, "causal"),
    ((1, 2, 128, 16), (1, 2, 128, 16), True, -1, "positive"),
    ((1, 2, 128, 16), (1, 2, 256, 16), True, 32, "as many keys"),
])
def test_a_window_the_mask_is_not_defined_for_is_refused(q, k, causal,
                                                         window, why):
    q, k = jnp.zeros(q), jnp.zeros(k)
    with pytest.raises(MXNetError, match=why):
        fa.flash_attention(q, k, k, causal, window=window)
    with pytest.raises(MXNetError, match=why):
        jax.grad(lambda q_: jnp.sum(fa.flash_attention(
            q_, k, k, causal, window=window)))(q)


def _closed_form(n, per_window):
    """Live pairs of ``n`` x ``n`` square blocks under a window of
    ``per_window`` whole blocks: per q block the diagonal, ``per_window -
    1`` whole blocks and the one the window's edge crosses, as far as the
    sequence's start allows."""
    return sum(min(i + 1, per_window + 1) for i in range(n))


@pytest.mark.parametrize("n,block,per_window,live,causal_live", [
    (16, 1024, 4, 70, 136),     # the cell's: 16,384 positions, 4,096 seen
    (16, 64, 4, 70, 136),       # the same ratio, scaled down
    (8, 128, 2, 21, 36),
    (4, 128, 1, 7, 10),
])
def test_block_pair_counters_equal_the_closed_form(n, block, per_window, live,
                                                   causal_live):
    assert _closed_form(n, per_window) == live
    assert _closed_form(n, n) == causal_live
    window = per_window * block

    def counts(w):
        fa.reset_dispatch_stats()
        fa.Mask(True, w).count_block_pairs(n, n, block, block)
        return fa.DISPATCH_STATS["block_pairs"]

    got = counts(window)
    assert got["visible"] + got["crossed"] == live
    assert got["skipped"] == n * n - live
    # crossed: every diagonal, and the window's edge from q block
    # ``per_window`` on
    assert got["crossed"] == n + max(n - per_window, 0)
    got = counts(0)
    assert got["visible"] + got["crossed"] == causal_live
    assert got["crossed"] == n
    # both grids hold the steps a block can need, no more
    assert fa.Mask(True, window).steps(n, n, block, block) == (
        min(per_window + 1, n), min(per_window + 1, n))


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128),
                                   (512, 128), (128, 512)])
@pytest.mark.parametrize("window", [1, 40, 128, 129, 300, 640, 1023])
def test_block_case_against_the_mask_itself(bq, bk, window):
    """Pair by pair at 1,024 positions: a pair called skipped holds no
    visible position, one called visible no masked one, one called crossed
    both; every live pair lies inside both kernels' shrunk grids; the
    index maps name a live block for every step, inside the array."""
    t = 1024
    i, j = np.arange(t)[:, None], np.arange(t)[None]
    seen = (j <= i) & (j > i - window)
    n_q, n_k = t // bq, t // bk
    mask = fa.Mask(True, window)
    k_steps, q_steps = mask.steps(n_q, n_k, bq, bk)
    for qi in range(n_q):
        for ki in range(n_k):
            tile = seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            visible, crossed = mask.block_case(qi, ki, bq, bk)
            assert bool(visible) == bool(tile.all())
            assert bool(visible or crossed) == bool(tile.any())
            if not (visible or crossed):
                continue
            k_lo = int(mask.first_k_block(qi, bq, bk))
            assert 0 <= ki - k_lo < k_steps
            assert 0 <= qi - ki * bk // bq < q_steps
            assert qi <= int(mask.last_q_block(ki, n_q, bq, bk))
