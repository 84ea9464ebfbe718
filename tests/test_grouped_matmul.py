"""The Pallas grouped-matmul kernel (``mxtpu/ops/pallas/grouped_matmul.py``)
under the interpreter, through the one place the routed layer chooses who
multiplies (``moe._grouped``): its three forms against a plain loop over
the groups, the layer's value and gradients against its masked form at a
rehearsal of two cells' ladders, and the reasons a product is left to
``ragged_dot``, counted."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import telemetry
from mxtpu.ops.pallas import grouped_matmul as gmm
from mxtpu.parallel import moe

K_, N_ = 128, 256

# name: (rows laid out, rows of each group); what lies past the groups'
# rows belongs to none. Three groups over 512 rows take tiles of 128
CASES = {
    "an_empty_group": (512, (200, 0, 312)),
    "a_group_smaller_than_a_tile": (512, (5, 300, 207)),
    "groups_that_straddle_tiles": (512, (130, 250, 132)),
    "rows_past_the_last_group": (640, (100, 0, 150)),
    "one_group_has_them_all": (512, (0, 512, 0)),
    "no_row_is_live": (384, (0, 0, 0)),
    "fewer_rows_than_a_tile": (40, (3, 30)),
}


@pytest.fixture()
def interpreted(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    for name in ("pallas", "xla"):
        telemetry.reset_metric("moe.grouped_mm." + name)


def _operands(form, rows, sizes, dtype, seed=0):
    """(a, b) of a product of ``form``, every row past the groups' NaN."""
    rng = np.random.default_rng(seed)
    held, live = len(sizes), sum(sizes)
    shape = {gmm.ROWS: (held, K_, N_), gmm.ROWS_T: (held, N_, K_),
             gmm.WEIGHTS: (rows, N_)}[form]
    a = rng.standard_normal((rows, K_)).astype(np.float32)
    b = rng.standard_normal(shape).astype(np.float32)
    a[live:] = np.nan
    if form == gmm.WEIGHTS:
        b[live:] = np.nan
    return jnp.asarray(a, dtype), jnp.asarray(b, dtype)


def _by_group(form, a, b, sizes):
    """The plain loop, float32 from the operands as they are: the live
    rows' products (``WEIGHTS``: every group's, an empty one's zero)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ends = np.cumsum(sizes)
    spans = [slice(e - s, e) for s, e in zip(sizes, ends)]
    if form == gmm.WEIGHTS:
        return np.stack([a[s].T @ b[s] for s in spans])
    width = b.shape[1 if form == gmm.ROWS_T else 2]
    return np.concatenate(
        [a[s] @ (b[e].T if form == gmm.ROWS_T else b[e])
         for e, s in enumerate(spans)] + [np.zeros((0, width), np.float32)])


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", [gmm.ROWS, gmm.ROWS_T, gmm.WEIGHTS])
def test_a_form_is_the_loop_over_its_groups(interpreted, form, dtype, case):
    """bf16 operands take the kernel: float32 sums, one rounding (2^-9 of
    a result at most). float32 operands are left to ``ragged_dot`` (reason
    ``dtype``). Rows past the last group go in as NaN and are selected
    away on the way out, as every caller does: none reaches a live row or
    a group's product."""
    rows, sizes = CASES[case]
    a, b = _operands(form, rows, sizes, jnp.dtype(dtype))
    out = jax.jit(lambda a, b, sizes: moe._grouped(
        a, b, moe._Groups(sizes, rows), form))(
            a, b, jnp.asarray(sizes, jnp.int32))
    assert out.dtype == a.dtype
    took = "pallas" if dtype == "bfloat16" else "xla"
    assert telemetry.value("moe.grouped_mm." + took) == 1
    assert telemetry.value("moe.grouped_mm.pallas") + telemetry.value(
        "moe.grouped_mm.xla") == 1
    if took == "xla":
        assert telemetry.tagged("moe.grouped_mm.xla") == {"dtype": 1}
    want = _by_group(form, a, b, sizes)
    got = np.asarray(out, np.float32)
    if form != gmm.WEIGHTS:
        got = got[:sum(sizes)]
    assert got.shape == want.shape and np.all(np.isfinite(got))
    scale = max(float(np.abs(want).max(initial=0.0)), 1.0)
    assert np.abs(got - want).max(initial=0.0) <= scale * (
        2.0 ** -8 if dtype == "bfloat16" else 1e-5)


@pytest.mark.parametrize("rows,held,tile,visits", [
    (512, 3, 128, 6), (65536, 8, 256, 263), (1536, 8, 128, 19),
    (40, 2, 40, 2)])
def test_the_table_visits_a_tile_once_a_group(rows, held, tile, visits):
    """The row tile follows rows / held downwards (ling3's lowest rung,
    1,536 rows for eight groups, takes 128); the table has room for every
    tile and one more visit for every group but one, its visits ascend in
    tile and group, cover each group's rows exactly and visit an empty
    group once."""
    assert gmm.row_tile(rows, held) == tile
    rng = np.random.default_rng(rows)
    cuts = np.sort(rng.integers(0, rows * 3 // 4, held - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [rows * 3 // 4]]))
    sizes[rng.integers(held)] = 0
    table = gmm.tile_table(jnp.asarray(sizes, jnp.int32), rows, tile)
    offsets, group_of, tile_of, n = (np.asarray(x) for x in table)
    assert group_of.shape == tile_of.shape == (visits,)
    assert list(offsets) == [0] + list(np.cumsum(sizes))
    n = int(n)
    assert n <= visits
    seen = list(zip(tile_of[:n], group_of[:n]))
    assert seen == sorted(set(seen))
    for g, size in enumerate(sizes):
        mine = [t for t, e in seen if e == g]
        if size:
            first, last = offsets[g] // tile, (offsets[g + 1] - 1) // tile
            assert mine == list(range(first, last + 1))
        else:
            assert len(mine) == 1


# (tokens, choices, experts held / scored, the router's groups / kept, the
# experts' width) at a row tile of 16: lfm2's ladder of two rungs, ling3's
# of six
LADDERS = {
    "lfm2": (128, 4, 8, 32, 1, 1, 256, (176, 512)),
    "ling3": (64, 8, 8, 512, 8, 4, 384, (16, 32, 64, 128, 256, 512)),
}


@pytest.mark.parametrize("cell", sorted(LADDERS))
def test_the_layer_is_its_masked_form(interpreted, monkeypatch, cell):
    """``routed_ffn`` in bf16, every product through the kernel, against
    ``grouped=False``: the value and the gradients of x, the router and
    the three expert leaves. Every branch of both switches is built: nine
    products a rung took the kernel and none was left to XLA, each branch
    built one table, and the kernel was traced once for each distinct
    (rows, form, widths): six a rung."""
    t, k, held, total, n_group, topk_group, f, rungs = LADDERS[cell]
    d = 128
    monkeypatch.setattr(moe, "_ROW_TILE", 16)
    assert moe._rungs(t * k, held, total) == rungs
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    leaf = lambda key, *s, scale=0.1: (scale * jax.random.normal(  # noqa: E731
        key, s, jnp.float32)).astype(jnp.bfloat16)
    x = leaf(keys[0], t, d, scale=1.0)
    router, bias = leaf(keys[1], total, d), jnp.zeros((total,), jnp.bfloat16)
    experts = (leaf(keys[2], held, d, f), leaf(keys[3], held, d, f),
               leaf(keys[4], held, f, d))
    tables = []
    tile_table = gmm.tile_table
    monkeypatch.setattr(gmm, "tile_table", lambda *a: tables.append(a[1:])
                        or tile_table(*a))
    tile_table.clear_cache()

    def grads(grouped):
        def loss(x, router, *experts):
            out = moe.routed_ffn(
                x, router, bias, *experts, top_k=k, scale=2.5,
                grouped=grouped, n_group=n_group, topk_group=topk_group)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
        (_, out), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, router, *experts)
        return (out,) + g

    traced = []
    pallas_call = gmm.pl.pallas_call
    monkeypatch.setattr(gmm.pl, "pallas_call", lambda *a, **named: (
        traced.append(named["name"]) or pallas_call(*a, **named)))
    got = grads(True)
    assert telemetry.value("moe.grouped_mm.pallas") == 9 * len(rungs)
    assert telemetry.value("moe.grouped_mm.xla") == 0
    assert sorted(tables) == sorted(
        (rows, gmm.row_tile(rows, held)) for rows in rungs * 2)
    assert sorted(traced) == sorted(
        ["grouped_matmul_" + form for form in (gmm.ROWS, gmm.ROWS_T,
                                               gmm.WEIGHTS)] * 2 * len(rungs))
    want = grads(False)
    idx, _ = moe.route_top_k(x, router, bias, k, 2.5, n_group=n_group,
                             topk_group=topk_group)
    assert int(moe.piece_plan(idx, 0, held, total).n_live) > 0
    f32 = lambda a: np.asarray(a, np.float32)   # noqa: E731
    for name, a, b in zip(("out", "dx", "drouter", "dgate", "dup", "ddown"),
                          got, want):
        assert np.all(np.isfinite(f32(a))), name
        gap = np.linalg.norm(f32(a) - f32(b)) / np.linalg.norm(f32(b))
        assert gap <= 3e-2, (name, gap)


def test_a_product_left_to_xla_is_counted_by_reason(monkeypatch):
    """Off the TPU without the interpreter (``platform``), operands wider
    than 2 bytes (``dtype``) and a width that 128 lanes do not tile
    (``lanes``): ``ragged_dot`` takes the product, the right one, and
    ``moe.grouped_mm.xla`` says why."""
    rows, sizes = CASES["an_empty_group"]
    assert (moe._ROWS, moe._ROWS_T, moe._WEIGHTS) == (
        gmm.ROWS, gmm.ROWS_T, gmm.WEIGHTS)      # the layer's names, by hand
    for name in ("pallas", "xla"):
        telemetry.reset_metric("moe.grouped_mm." + name)

    def product(a, b, form=gmm.ROWS):
        out = moe._grouped(a, b, moe._Groups(
            jnp.asarray(sizes, jnp.int32), rows), form)
        want = _by_group(form, a, b, sizes)
        got = np.asarray(out, np.float32)[:want.shape[0]]
        assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()

    a, b = _operands(gmm.ROWS, rows, sizes, jnp.bfloat16)
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    product(a, b)
    assert telemetry.tagged("moe.grouped_mm.xla") == {"platform": 1}
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    product(a.astype(jnp.float32), b.astype(jnp.float32))
    product(a, b.astype(jnp.float32))
    product(a[:, :96], b[:, :96])
    product(a, b[:, :, :200])
    product(a, jnp.swapaxes(b, 1, 2)[:, :200], gmm.ROWS_T)
    assert telemetry.tagged("moe.grouped_mm.xla") == {
        "platform": 1, "dtype": 2, "lanes": 3}
    assert telemetry.value("moe.grouped_mm.xla") == 6
    assert telemetry.value("moe.grouped_mm.pallas") == 0
    product(a, b)
    assert telemetry.value("moe.grouped_mm.pallas") == 1
