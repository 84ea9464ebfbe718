"""The forms of ``flash_attention.Mask`` agree with its definition.

A mask is written once (``Mask.seen``, position by position; a selection's
array) and read in four other forms: by block (``block_case``, ``live``),
on a kernel's transposed tile (``on_tile``), on a plain path's scores
(``on_scores``) and as the cut of both kernels' grids (``steps``, ``k_at``
/ ``q_at``, the index maps ``k_block`` / ``q_block``, the k blocks at
which a q block's rows of dq open and leave). A form that disagrees with
the definition is a wrong answer on the chip that no test of the plain
path sees; every kind of mask, and every bound added to one, passes
through here."""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")

T = 512
# a window narrower than a block, one wider and no multiple of any, one of
# whole blocks; the selection keeps about a third of the causal pairs
KINDS = {
    "full": fa.Mask(),
    "causal": fa.Mask(True),
    "window_40": fa.Mask(True, 40),
    "window_300": fa.Mask(True, 300),
    "window_256": fa.Mask(True, 256),
    "selected": fa.Mask(True, selected=True),
}


def _selection():
    """[1, T, T] int8, keys first: a causal set a query, never empty."""
    rng = np.random.RandomState(5)
    k, q = np.arange(T)[:, None], np.arange(T)[None, :]
    return ((rng.rand(T, T) < 0.3) & (k <= q) | (k == q)).astype(
        np.int8)[None]


def _dense(mask, selection):
    """The definition over [T queries, T keys]."""
    if mask.selected:
        return selection[0].T != 0
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    return np.broadcast_to(mask.seen(i, j), (T, T))


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_form_of_the_mask_is_its_definition(kind, bq, bk):
    mask = KINDS[kind]
    selection = _selection() if mask.selected else None
    dense = _dense(mask, selection)
    n_q, n_k = T // bq, T // bk
    unmasked = lambda x: np.asarray(x) > 0.5 * fa._NEG_INF

    # a plain path's scores, whole and a k block at a time
    s = jnp.zeros((1, 1, T, T), jnp.float32)
    assert (unmasked(mask.on_scores(s, selection))[0, 0] == dense).all()
    for ki in range(n_k):
        rows = None if selection is None else selection[:, ki * bk:][:, :bk]
        got = mask.on_scores(s[..., :bk], rows, jnp.arange(T), ki * bk)
        assert (unmasked(got)[0, 0] == dense[:, ki * bk:][:, :bk]).all()

    # by block and on the kernels' transposed tile
    live = np.zeros((n_q, n_k), bool)
    for qi in range(n_q):
        for ki in range(n_k):
            want = dense[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            visible, crossed = mask.block_case(qi, ki, bq, bk)
            assert not (visible and crossed)
            live[qi, ki] = mask.live(qi, ki, bq, bk)
            assert live[qi, ki] == bool(visible or crossed)
            if not live[qi, ki]:
                assert not want.any()       # skipped: nothing to see
                continue
            if not mask.selected:           # geometry sorts exactly
                assert bool(visible) == bool(want.all())
                assert want.any()
            tile = None if selection is None else jnp.asarray(
                selection[0, ki * bk:(ki + 1) * bk, qi * bq:(qi + 1) * bq])
            got = mask.on_tile(jnp.zeros((bk, bq), jnp.float32), qi, ki, bq,
                               bk, tile=tile)
            assert (unmasked(got).T == want).all(), (qi, ki)
            # a slab of the tile's columns, as the forward walks it
            half = bq // 2
            got = mask.on_tile(jnp.zeros((bk, half), jnp.float32), qi, ki,
                               bq, bk, half,
                               None if tile is None else tile[:, half:])
            assert (unmasked(got).T == want[half:]).all(), (qi, ki)
    assert mask.block_pairs(n_q, n_k, bq, bk) == tuple(
        int(np.sum([[mask.block_case(qi, ki, bq, bk)[c] for ki in range(n_k)]
                    for qi in range(n_q)])) for c in (0, 1))

    # the grids' cut: every live pair is computed once, by both kernels,
    # and every step names a block inside the arrays
    k_steps, q_steps = mask.steps(n_q, n_k, bq, bk)
    assert 1 <= k_steps <= n_k and 1 <= q_steps <= n_q
    forward, backward = [], []
    for qi in range(n_q):
        for step in range(k_steps):
            ki = int(mask.k_at(qi, step, bq, bk))
            assert 0 <= int(mask.k_block(qi, step, bq, bk)) < n_k
            if ki < n_k and mask.live(qi, ki, bq, bk):
                forward.append((qi, ki))
                assert int(mask.k_block(qi, step, bq, bk)) == ki
    for ki in range(n_k):
        for step in range(q_steps):
            qi = int(mask.q_at(ki, step, bq, bk))
            assert 0 <= int(mask.q_block(ki, step, bq, bk, n_q)) < n_q
            if qi < n_q and mask.live(qi, ki, bq, bk):
                backward.append((qi, ki))
                assert int(mask.q_block(ki, step, bq, bk, n_q)) == qi
    want = sorted(zip(*np.nonzero(live)))
    assert sorted(forward) == want and len(set(forward)) == len(forward)
    assert sorted(backward) == want and len(set(backward)) == len(backward)

    # a q block's rows of dq open and leave at steps the backward's grid
    # holds, and every live pair of the block lies between them
    for qi in range(n_q):
        first = int(mask.first_k_block(qi, bq, bk))
        last = int(mask.last_k_block(qi, n_k, bq, bk))
        mine = [ki for q, ki in want if q == qi]
        assert first <= min(mine) and max(mine) <= last < n_k
        for ki in (first, last):
            step = [s_ for s_ in range(q_steps)
                    if int(mask.q_at(ki, s_, bq, bk)) == qi]
            assert len(step) == 1, (qi, ki)


def test_a_mask_refuses_what_it_is_not_defined_for():
    from mxtpu.base import MXNetError
    q = jnp.zeros((1, 1, 256, 8))
    assert fa.Mask.of(q, q, True, 64) == fa.Mask(True, 64)
    assert fa.Mask.of(q, q, 1, 0) == fa.Mask(True)
    assert fa.Mask.of(q, q, True, 256) == fa.Mask(True)     # masks nothing
    for causal, window, k in ((False, 64, q), (True, -1, q),
                              (True, 64, q[:, :, :128])):
        with pytest.raises(MXNetError, match="window="):
            fa.Mask.of(q, k, causal, window)
    assert [fa.Mask(True, w, s).name("fwd") for w, s in
            ((0, False), (64, False), (0, True))] == [
        "flash_attention_fwd", "flash_window_fwd", "sparse_attention_fwd"]
    assert fa.Mask(True).vmem(512, 1024) == 0
    assert fa.Mask(True, selected=True).vmem(512, 1024) == 2 * 512 * 1024
