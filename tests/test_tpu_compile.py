"""The main path's Pallas kernels compile for the real chip, at real widths.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is *described*, not attached (``jax.experimental.topologies``). Each
case lowers a public kernel entry at a BERT-base / ResNet-50 shape for one
chip of a ``v5e:2x2`` and asserts Mosaic accepted it — what interpret mode
cannot show (tiling, VMEM, lowering refusals). Nothing runs, so nothing here
is a result or a time.

The topology is described inside a module-scoped fixture and NEVER while a
module is imported: only one process may hold libtpu, and an import-time
call under several xdist workers makes the workers collect different tests
and the whole suite count 0. Keep these cases in this one file for the same
reason (a second file can land on a worker that cannot load the library).
"""
import importlib
import re
import time

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler / libtpu held
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_tpu(monkeypatch):
    """Steer the dispatchers' platform check (they ask
    ``jax.devices()[0].platform``, which is the CPU here) and keep the
    persistent compilation cache off: a described-chip compile is written
    to it but cannot be read back without a chip, and warns."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


# BERT-base attention: B16 H12 T512 D64, zero-padded to the 128-lane
# granule inside the entry point (bench.py bert_base / chip_smoke.py)
_QKV = (16, 12, 512, 64)


@pytest.mark.parametrize("causal", [False, True],
                         ids=["bert_base", "causal"])
def test_flash_forward_compiles_to_mosaic(one_chip, as_tpu, causal):
    q = _spec(_QKV, one_chip)
    fa.reset_dispatch_stats()
    text = _compiled_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal), q, q, q)
    assert "tpu_custom_call" in text
    assert fa.DISPATCH_STATS["pallas"] >= 1
    assert not fa.DISPATCH_STATS["fallback_reasons"]


@pytest.mark.parametrize("causal", [False, True],
                         ids=["bert_base", "causal"])
def test_flash_backward_compiles_to_mosaic(one_chip, as_tpu, causal):
    """Forward and backward kernels as one differentiated program: both
    are Mosaic calls under their stable names, the backward did not take
    the blockwise XLA path, and no float32 [T, T] matrix is in the
    program (the blockwise path made four of them a layer)."""
    q = _spec(_QKV, one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal).astype(jnp.float32).sum()

    fa.reset_dispatch_stats()
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") >= 2
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "f32[16,12,512,512]" not in text and "f32[192,512,512]" not in text
    assert fa.DISPATCH_STATS["bwd_pallas"] == 1
    assert fa.DISPATCH_STATS["bwd_xla"] == 0


def test_flash_backward_with_lse_compiles_to_mosaic(one_chip, as_tpu):
    """What ring attention differentiates: causal, Tq != Tk, several q and
    k blocks, a cotangent on lse, head dim on the lane granule."""
    q = _spec((2, 8, 1024, 128), one_chip)
    kv = _spec((2, 8, 2048, 128), one_chip)

    def loss(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, True)
        return out.astype(jnp.float32).sum() + jnp.sin(lse).sum()

    fa.reset_dispatch_stats()
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert "flash_attention_bwd" in text
    assert fa.DISPATCH_STATS["bwd_pallas"] == 1
    assert fa.DISPATCH_STATS["bwd_xla"] == 0


# ResNet-50 conv classes at batch 128 (NHWC x, HWIO w, stride, padding)
_CONVS = {
    "stem_7x7s2": ((128, 224, 224, 3), (7, 7, 3, 64), (2, 2),
                   ((3, 3), (3, 3))),
    "1x1_64_256": ((128, 56, 56, 64), (1, 1, 64, 256), (1, 1),
                   ((0, 0), (0, 0))),
    "3x3_64_64": ((128, 56, 56, 64), (3, 3, 64, 64), (1, 1),
                  ((1, 1), (1, 1))),
}


@pytest.mark.parametrize("name", sorted(_CONVS))
def test_conv_route_compiles_to_xla_convolutions(one_chip, as_tpu, name):
    """The one route (``ops.nn.conv_fast``), forward and both gradients at
    the cell's batch: three XLA convolutions in bf16, no hand kernel."""
    from mxtpu.ops.nn import conv_fast
    xs, ws, strides, padding = _CONVS[name]

    def loss(x, w):
        y = conv_fast(x, w, strides, padding, (1, 1), (1, 1),
                      ("NHWC", "HWIO", "NHWC"), 1)
        assert y.dtype == jnp.bfloat16
        return y.astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)),
                          _spec(xs, one_chip), _spec(ws, one_chip))
    assert "tpu_custom_call" not in text
    assert len(re.findall(r" convolution\(", text)) == 3
    assert "bf16[%s]" % ",".join(map(str, xs)) in text     # dx
    assert "bf16[%s]" % ",".join(map(str, ws)) in text     # dw


def test_interpret_flag_is_an_error_on_tpu(as_tpu, monkeypatch):
    """No hidden slow path: the interpreter flag is the off-chip parity
    route, and a TPU run that still carries it refuses to start."""
    from mxtpu.base import MXNetError
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    with pytest.raises(MXNetError, match="MXTPU_FLASH_INTERPRET"):
        fa._interpret()


# latent attention at the kanana2_30b_a3b cell's shape: keys and queries
# 192 wide (blocks of 192 lanes, unpadded), values 128, one sequence of
# 8,192 over 16 x 16 causal blocks
def test_flash_two_widths_compile_to_mosaic(one_chip, as_tpu):
    q = _spec((1, 32, 8192, 192), one_chip)
    v = _spec((1, 32, 8192, 128), one_chip)
    fa.reset_dispatch_stats()

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True).astype(
            jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, v)
    assert text.count("tpu_custom_call") >= 2
    assert "bf16[32,8192,192]" in text and "bf16[32,8192,128]" in text
    assert "bf16[32,8192,256]" not in text
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1)
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0


# grouped heads at the lfm2_8b_a1b cell's shape: two sequences, 32 query
# heads over 8 key/value heads of 64 (padded to the 128 lanes), 8,192
# causal positions; dk and dv of a whole key/value head wait in VMEM
def test_flash_grouped_heads_compile_to_mosaic(one_chip, as_tpu):
    q = _spec((2, 32, 8192, 64), one_chip)
    kv = _spec((2, 8, 8192, 64), one_chip)
    fa.reset_dispatch_stats()

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True).astype(
            jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") >= 2
    # K and V enter both kernels padded at their own 8 heads (16 rows
    # flattened); nothing of 64 rows flattened is float32 (no partials a
    # query head), and K's and V's gradients leave at 8 heads
    assert "bf16[16,8192,128]" in text
    assert "f32[64,8192,128]" not in text
    assert "bf16[2,8,8192,64]" in text
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1)
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0
    assert stats["grouped"] == 1 and stats["kv_repeated"] == 0


# a window / global stack's attention at the smallthinker_21b_a3b cell's
# shape: 28 query heads over 4 key/value heads of 128, unpadded, one
# sequence of 16,384; dq of a head and dk, dv of a whole key/value head over
# its seven query heads wait in VMEM, so the backward's q block halves
@pytest.mark.parametrize("window", [4096, 0], ids=["windowed", "global"])
def test_flash_window_compiles_to_mosaic(one_chip, as_tpu, window):
    q = _spec((1, 28, 16384, 128), one_chip)
    kv = _spec((1, 4, 16384, 128), one_chip)
    fa.reset_dispatch_stats()

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, window=window)
                       .astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") >= 2
    # a kernel's ``name=`` is its instruction's name (the scope
    # ``flash_attention_bwd`` is on both calls' prologue and epilogue)
    kernels = set(re.findall(
        r"%\w*?(flash_(?:window|attention)_(?:fwd|bwd))[_.\d]* = \(", text))
    assert kernels == ({"flash_window_fwd", "flash_window_bwd"} if window
                       else {"flash_attention_fwd", "flash_attention_bwd"})
    assert "bf16[1,4,16384,128]" in text        # dk, dv at the 4 heads
    assert fa._plan(q, kv, kv, fa.Mask(True, window), fa._BLOCK_Q,
                    fa._BLOCK_K, "backward") == ((512, 1024), None)
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1)
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0
    assert stats["grouped"] == 1 and stats["kv_repeated"] == 0
    assert stats["windowed"] == (1 if window else 0)
    assert stats["window_unskipped"] == 0
    # the forward's blocks of 1,024: 70 live pairs a windowed head where
    # the causal one has 136, of 256
    pairs = stats["block_pairs"]
    assert pairs["visible"] + pairs["crossed"] == (70 if window else 136)
    assert pairs["skipped"] == 256 - (70 if window else 136)
    # what ``flash_window_visit_ratio.train`` reads in that cell: 1.25
    assert stats["window_pairs_seen"] == (58722304 if window else 0)
    assert stats["window_pairs_visited"] == (70 * 1024 * 1024 if window
                                             else 0)


# the laguna_s_2_1 cell's windowed layers: 72 query heads over 8 key/value
# heads of 128 (nine a group), one sequence of 16,384, a window of 512:
# narrower than the 1,024 x 1,024 blocks a call asks for by default, so
# the call asks for 512 x 512 (``Mask.blocks``) and visits twice the
# window's pairs where 1,024-wide blocks visit 3.9 times
def test_flash_narrow_window_compiles_to_mosaic(one_chip, as_tpu):
    q = _spec((1, 72, 16384, 128), one_chip)
    kv = _spec((1, 8, 16384, 128), one_chip)
    fa.reset_dispatch_stats()

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, window=512)
                       .astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    kernels = set(re.findall(
        r"%\w*?(flash_(?:window|attention)_(?:fwd|bwd))[_.\d]* = \(", text))
    assert kernels == {"flash_window_fwd", "flash_window_bwd"}
    assert "bf16[1,8,16384,128]" in text        # dk, dv at the 8 heads
    mask = fa.Mask(True, 512)
    assert mask.blocks(None, None) == (512, 512)
    assert mask.blocks(1024, None) == (1024, 512)      # a caller's is kept
    assert fa.Mask(True, 4096).blocks(None, None) == (1024, 1024)
    assert fa.Mask(True).blocks(None, None) == (1024, 1024)
    for direction in ("forward", "backward"):
        assert fa._plan(q, kv, kv, mask, 512, 512, direction) == (
            (512, 512), None)
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1)
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0
    assert stats["grouped"] == 1 and stats["kv_repeated"] == 0
    assert stats["windowed"] == 1 and stats["window_unskipped"] == 0
    # q block i visits k blocks i - 1 and i: 63 of 1,024 block pairs
    pairs = stats["block_pairs"]
    assert pairs["visible"] + pairs["crossed"] == 63
    assert stats["window_pairs_seen"] == 512 * 16384 - 512 * 511 // 2
    assert stats["window_pairs_visited"] == 63 * 512 * 512
    assert 1.99 < stats["window_pairs_visited"] / stats[
        "window_pairs_seen"] < 2.01



def test_routed_experts_compile_to_grouped_kernels(one_chip, as_tpu):
    """The expert layer at the cell's widths (16 of 128 experts held, 6
    choices a token, 8,192 tokens): every grouped product, forward and both
    transposes, is a call of the Pallas grouped-matmul kernel under its own
    name and none is left to ``ragged_dot``; nothing is expanded into a
    product over every expert held; a forward branch holds three calls and
    a backward branch six (the hand-written backward computes none again:
    nine before); no branch holds a copy of an expert leaf, transposed or
    not (``a @ b[e].T`` reads the leaf as it lies): the only arrays of a
    leaf's shape made in a branch are the three weight gradients, the
    kernel's own results; the lowered module holds ONE function for each
    distinct (rows, widths, form), six a rung, called from every site that
    has the shape; and the only arrays of all T*k = 49,152 (token, slot)
    rows computed unconditionally are the two up products the forward keeps
    for the backward, bf16 by the experts' width: none by the model's
    width, none float32."""
    from _hlo_text import (arrays_outside_control_flow, branch_computations,
                           grouped_kernels, producers)
    from mxtpu import telemetry
    from mxtpu.parallel import moe
    x = _spec((8192, 2048), one_chip)
    specs = (x, _spec((128, 2048), one_chip), _spec((128,), one_chip),
             _spec((16, 2048, 768), one_chip),
             _spec((16, 2048, 768), one_chip),
             _spec((16, 768, 2048), one_chip))

    def loss(x, router, bias, eg, eu, ed):     # one that needs the output
        return jnp.sum(jnp.sin(moe.routed_ffn(
            x, router, bias, eg, eu, ed, top_k=6,
            scale=2.448).astype(jnp.float32)))

    for name in ("pallas", "xla"):
        telemetry.reset_metric("moe.grouped_mm." + name)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(*specs)
    assert moe._rungs(49152, 16, 128) == (8192, 16384, 49152)
    assert [telemetry.value("moe.grouped_mm." + name)
            for name in ("pallas", "xla")] == [27, 0]
    # one lowered function a (rows, widths, form): gate and up share theirs
    assert lowered.as_text().count("tpu_custom_call") == 6 * 3
    text = lowered.compile().as_text()
    assert "ragged-dot" not in text and "tpu_custom_call" in text
    # no [T*k, held, .] or [held, T*k, .] expansion of the products
    assert "49152,16,768" not in text and "16,49152,768" not in text
    assert "[8192,768]" in text and "[49152,768]" in text
    # three rungs, forward | backward
    assert grouped_kernels(text) == [3, 3, 3, 6, 6, 6]
    for branches in branch_computations(text):
        for lines in branches:
            made = producers(lines, (16, 2048, 768), (16, 768, 2048))
            assert all(op == "get-tuple-element" or name.startswith(
                "grouped_matmul_weights") for name, op in made), made
            assert sum(op == "custom-call" for _, op in made) in (0, 3)
    assert arrays_outside_control_flow(text, 49152, 2048) == []
    kept = arrays_outside_control_flow(text, 49152, 768)
    assert kept and all(line.count("[49152,768]")
                        == line.count("bf16[49152,768]") for line in kept)


def test_last_rung_sums_by_token_with_a_gather(one_chip, as_tpu):
    """The expert layer at lfm2's widths (8 of 32 experts held, 4 choices a
    token, two sequences of 8,192; the ladder 22,016 / 65,536 of 65,536
    pairs) with its gradient: the last rung's two branches hold no float32
    ``[*, 2048]`` scatter with an add combiner (each sums by token with a
    gather a slot, four of ``[16384,2048]``, bf16 forward and float32
    backward, from ``pos``, an ``s32[65536]`` unique scatter built inside
    the branch); the low rung's backward, a third of the pairs,
    still scatter-adds its 22,016 float32 rows. Forced to the scatter-add
    everywhere (the parent's form) all four branches hold one. Prints
    ``memory_analysis()``'s temporaries of both."""
    from _hlo_text import (branch_computations, grouped_kernels,
                           is_grouped_kernel)
    from mxtpu.parallel import moe
    specs = (_spec((16384, 2048), one_chip), _spec((32, 2048), one_chip),
             _spec((32,), one_chip), _spec((8, 2048, 1792), one_chip),
             _spec((8, 2048, 1792), one_chip),
             _spec((8, 1792, 2048), one_chip))
    assert moe._rungs(65536, 8, 32) == (22016, 65536)

    def compiled():
        def loss(x, router, bias, eg, eu, ed):  # a new function a form
            return jnp.sum(jnp.sin(moe.routed_ffn(
                x, router, bias, eg, eu, ed, top_k=4).astype(jnp.float32)))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
            *specs).compile()

    def forms(text):
        """[forward, backward] x [low rung, last rung]: (float32 [*, 2048]
        scatter-adds, gathers of a slot's rows, unique scatters of ``pos``)."""
        switches = branch_computations(text)
        assert [len(branches) for branches in switches] == [2, 2]
        # the backward's branches hold six grouped kernels, the forward's 3
        switches.sort(key=lambda branches: sum(
            map(is_grouped_kernel, branches[0])))
        return [[(sum(" scatter(" in line and "f32[16384,2048]" in
                      line.split(" scatter(")[0] for line in lines),
                  sum(" gather(" in line and "[16384,2048]" in
                      line.split(" gather(")[0] for line in lines),
                  sum(" scatter(" in line and "s32[65536]" in
                      line.split(" scatter(")[0] for line in lines))
                 for lines in branches] for branches in switches]

    change = compiled()
    assert grouped_kernels(change.as_text()) == [3, 3, 6, 6]
    fwd, bwd = forms(change.as_text())
    assert fwd[1] == (0, 4, 1) and bwd[1] == (0, 4, 1)
    assert bwd[0] == (1, 0, 0)
    assert fwd[0] in ((1, 0, 0), (0, 4, 1))     # as the constants say
    assert bool(fwd[0][1]) == moe._sums_by_gather(22016, 65536, 2)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_sums_by_gather", lambda *shape: False)
        parent = compiled()
    assert forms(parent.as_text()) == [[(1, 0, 0)] * 2] * 2
    temps = [c.memory_analysis().temp_size_in_bytes for c in (parent, change)]
    print("lfm2 layer's gradient, temporaries: scatter-add everywhere %d B, "
          "the predicate's forms %d B" % tuple(temps))
    assert temps[1] <= temps[0] + (64 << 20)


def test_vocabulary_head_loss_reads_the_logits_once(one_chip, as_tpu):
    """BERT's head and loss with their gradient (``[16,512,768] x
    [30522,768]`` through ``nn.Dense(flatten=False)`` and
    ``SoftmaxCrossEntropyLoss``): the only result of 8,192 x 30,522 elements
    is the forward product's logits. Spelled ``pick(log_softmax(pred))`` the
    loss cost a relayout ``copy`` of them, a second ``bf16[8192,30522]``
    (the log-softmax) and a gather over it: 1.00 GB of temporaries, three
    operations and 3 ms of a 51 ms step (PERF.md, PR 33)."""
    from mxtpu import gluon
    from mxtpu.parallel.train import pure_forward
    head = gluon.nn.Dense(30522, use_bias=False, flatten=False, in_units=768)
    head.initialize()
    head.cast("bfloat16")
    head_fn, _ = pure_forward(head, train=True)
    loss_fn, _ = pure_forward(gluon.loss.SoftmaxCrossEntropyLoss(),
                              train=True)

    def loss(w, x, y):
        logits = head_fn([w], x).reshape((-1, 30522))
        return jnp.mean(loss_fn([], logits, y.reshape((-1,)))
                        .astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        _spec((30522, 768), one_chip), _spec((16, 512, 768), one_chip),
        _spec((16, 512), one_chip, jnp.float32)).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):].splitlines()
    whole = [line for line in entry
             if re.search(r"\[(16,512|8192),30522\]", line)
             and "get-tuple-element(" not in line]
    assert len(whole) == 1, whole
    assert " fusion(" in whole[0] and "kind=kOutput" in whole[0]
    assert " gather(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


# one layer of the keye_vl2_30b_a3b cell: 32 query heads over 4 key/value
# heads of 128, 16,384 positions, an indexer of 16 heads of 64, 2,048 keys
_SPARSE_T = 16384


def test_sparse_kernels_compile_to_mosaic(one_chip, as_tpu):
    """Both sparse kernels at the cell's shapes as one differentiated
    program: Mosaic calls under their own names, K and V at their own four
    heads, no [32, T, T] (or [T, T] float32) array in the program, and no
    call of the dense kernels beside them."""
    from mxtpu import telemetry
    t = _SPARSE_T
    q, kv = _spec((1, 32, t, 128), one_chip), _spec((1, 4, t, 128), one_chip)
    sets = _spec((1, t, t), one_chip, jnp.int8)

    def loss(q, k, v, sets):
        return fa.sparse_attention(q, k, v, sets, topk=2048).astype(
            jnp.float32).sum()

    fa.reset_dispatch_stats()
    for name in ("calls", "fallbacks", "bwd_pallas"):
        telemetry.reset_metric("sparse_attention." + name)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, sets)
    assert "sparse_attention_fwd" in text and "sparse_attention_bwd" in text
    assert "flash_attention_fwd" not in text
    assert "[32,16384,16384]" not in text and "f32[16384,16384]" not in text
    assert "bf16[1,32,16384,128]{3,2,1,0} broadcast" not in text
    assert [telemetry.value("sparse_attention." + n)
            for n in ("calls", "fallbacks", "bwd_pallas")] == [1, 0, 1]
    assert fa.DISPATCH_STATS["pallas"] == 0 and fa.DISPATCH_STATS["xla"] == 0


def test_kda_kernels_compile_to_mosaic(one_chip, as_tpu):
    """Both Kimi-Delta-Attention kernels at the Ling cell's shapes (one
    layer, 8,192 positions, 32 heads of 128, bf16 with a float32 log-decay)
    as one differentiated program: Mosaic calls under their own names, the
    heads fetched from the projections' own [B, T, H * K] layout (no copy
    of an operand), and nothing a token kept between them: the states at
    the 128 chunk starts, 268 MB, are the program's only temporary."""
    from mxtpu import telemetry
    kda = importlib.import_module("mxtpu.ops.pallas.kda")
    t = 8192
    x = _spec((1, t, 4096), one_chip)
    g = _spec((1, t, 4096), one_chip, jnp.float32)
    beta = _spec((1, t, 32), one_chip)

    def loss(*a):
        return kda.kda_attention(*a).astype(jnp.float32).sum()

    for name in ("calls", "fallbacks"):
        telemetry.reset_metric("kda_attention." + name)
    t0_us = time.perf_counter_ns() // 1000
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, g, beta).compile()
    text = compiled.as_text()
    # JAX reports a trace for every jnp call of the kernels' bodies, and
    # each is a ring event: the cell's step (six such layers, the forward
    # traced again under recomputation) has to leave the ring its head,
    # or every reader of the program's spans falls silent there
    traces = sum(1 for n, _c, ts, _d, _t in telemetry.events()
                 if n == "jax.trace" and ts >= t0_us)
    assert 0 < 6 * 2 * traces < telemetry.EVENT_RING_CAP // 2, traces
    assert "kda_fwd" in text and "kda_bwd" in text
    assert [telemetry.value("kda_attention." + n)
            for n in ("calls", "fallbacks")] == [1, 0]
    # a state a chunk and head, never a state a token
    assert "f32[32,128,128,128]" in text
    assert "f32[1,8192,32,128,128]" not in text
    assert "f32[32,8192,128,128]" not in text
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert 32 * 128 * 128 * 128 * 4 <= temps < 0.3e9, temps
    # the forward alone keeps nothing
    alone = jax.jit(lambda *a: kda.kda_attention(*a)).lower(
        x, x, x, g, beta).compile()
    assert alone.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("head_dim", [128, 0])
def test_filter_kernels_compile_to_mosaic(one_chip, as_tpu, head_dim):
    """The short filter's Pallas pair at the Ling cell's shape (8,192
    positions of 4,096 channels, bf16, four taps; with a head's norm and
    without): Mosaic calls under their own names, and no copy of the
    projection beside them: the value's program holds no temporary, the
    gradients' only the eight partial sums a tap of ``d weight``."""
    from mxtpu import telemetry
    from mxtpu.ops.registry import get_op
    x, w = _spec((1, 8192, 4096), one_chip), _spec((4096, 4), one_chip)
    op = get_op("_contrib_kda_conv").fn
    for name in ("calls", "pallas", "xla"):
        telemetry.reset_metric("kda_conv." + name)
    value = jax.jit(lambda x, w: op(x, w, head_dim=head_dim)).lower(
        x, w).compile()
    assert "kda_conv_fwd" in value.as_text()
    assert value.memory_analysis().temp_size_in_bytes < 1 << 20
    grads = jax.jit(jax.grad(lambda x, w: op(x, w, head_dim=head_dim).astype(
        jnp.float32).sum(), (0, 1))).lower(x, w).compile()
    text = grads.as_text()
    assert "kda_conv_bwd" in text and "f32[4,8,4096]" in text
    # a pass of [8192, 4096] bf16 is 64 MiB: none is kept beside the
    # cotangent (the sum's own broadcast, which a step never has)
    assert grads.memory_analysis().temp_size_in_bytes < (64 << 20) + (2 << 20)
    assert [telemetry.value("kda_conv." + n)
            for n in ("calls", "pallas", "xla")] == [3, 3, 0]


def test_kda_layer_compiles_to_the_filter_kernels(one_chip, as_tpu,
                                                  monkeypatch):
    """A Kimi-Delta-Attention layer at the Ling cell's widths (2,560 wide,
    32 heads of 128, 8,192 positions, bf16) as one differentiated program:
    three calls of ``kda_conv_fwd`` and three of ``kda_conv_bwd`` beside
    the KDA pair; nothing else takes a name that starts ``kda_fwd`` or
    ``kda_bwd`` (``benchmark/kernel_roofline.py`` sums by that prefix);
    the filter's bodies are traced once a (shape, ``head_dim``), two of
    each for the layer's six passes, and lowered to as many functions; and
    the traces a layer reports still leave the event ring its head at the
    cell's twelve (six layers, the forward again under recomputation)."""
    import mxtpu as mx
    from mxtpu import telemetry
    from mxtpu.gluon.block import _run_traced
    from mxtpu.gluon.model_zoo import hybrid_lm
    short_filter = importlib.import_module("mxtpu.ops.pallas.short_filter")
    layer = hybrid_lm.KimiDeltaAttention(2560, 32, 128, prefix="kda_")
    params = list(layer.collect_params().values())
    inner = {"kda_onorm_gamma": 128, "kda_proj_weight": 4096}
    datas = [_spec(tuple(d or inner.get(p.name, 2560) for d in p.shape),
                   one_chip) for p in params]

    def loss(datas, x):
        out, _ = _run_traced(params, datas, jax.random.PRNGKey(0), True,
                             lambda: layer(mx.nd.NDArray(x)))
        return out._data.astype(jnp.float32).sum()

    bodies = {"fwd": 0, "bwd": 0}
    for name in bodies:
        kernel = getattr(short_filter, "_%s_kernel" % name)

        def counting(*a, _kernel=kernel, _name=name, **kw):
            bodies[_name] += 1
            return _kernel(*a, **kw)

        monkeypatch.setattr(short_filter, "_%s_kernel" % name, counting)
    short_filter._forward.clear_cache()
    short_filter._backward.clear_cache()
    for name in ("kda_conv.calls", "kda_conv.pallas", "kda_conv.xla",
                 "kda_attention.fallbacks"):
        telemetry.reset_metric(name)
    t0_us = time.perf_counter_ns() // 1000
    lowered = jax.jit(jax.grad(loss)).lower(datas,
                                            _spec((1, 8192, 2560), one_chip))
    traces = sum(1 for n, _c, ts, _d, _t in telemetry.events()
                 if n == "jax.trace" and ts >= t0_us)
    assert 0 < 6 * 2 * traces < telemetry.EVENT_RING_CAP // 2, traces
    assert bodies == {"fwd": 2, "bwd": 2}
    assert [telemetry.value("kda_conv." + n)
            for n in ("calls", "pallas", "xla")] == [6, 6, 0]
    assert telemetry.value("kda_attention.fallbacks") == 0
    # one lowered function a body, called from every site of its shape:
    # the filter's four and the KDA pair
    assert lowered.as_text().count("tpu_custom_call") == 4 + 2
    text = lowered.compile().as_text()
    kernels = re.findall(r"%(kda_\w+?)(?:\.\d+)? = .* custom-call\(", text)
    assert sorted(kernels) == ["kda_bwd", "kda_conv_bwd", "kda_conv_bwd",
                               "kda_conv_bwd", "kda_conv_fwd", "kda_conv_fwd",
                               "kda_conv_fwd", "kda_fwd"], kernels


def test_the_selection_compiles_by_blocks(one_chip, as_tpu):
    """``_contrib_index_select`` at the cell's shapes: the sets leave as
    one int8 [1, T, T]; no float32 array of the whole square is alive (a
    block of 2,048 queries against 16,384 keys is the largest), and no
    sort: the edge of a set is counted out."""
    from mxtpu.ops.registry import get_op
    t = _SPARSE_T
    select = get_op("_contrib_index_select").fn
    compiled = jax.jit(lambda *a: select(*a, num_heads=16, topk=2048)).lower(
        _spec((1, t, 2048), one_chip), _spec((1024, 2048), one_chip),
        _spec((64, 2048), one_chip), _spec((16, 2048), one_chip)).compile()
    text = compiled.as_text()
    assert "s8[1,16384,16384]" in text
    assert "f32[1,16384,16384]" not in text and "f32[16384,16384]" not in text
    assert "f32[1,16384,2048]" in text
    assert " sort(" not in text and "TopK" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_grouped_attention_turns_through_the_rotary_kernels(one_chip,
                                                            as_tpu):
    """``grouped_attention`` at Laguna's windowed shape (16,384 positions,
    72 query heads over 8 key/value heads of 128, a window of 512, bf16):
    q and k are turned by ``rotary_turn``, one call each, heads first and
    in place, and their cotangents come back through ``rotary_unturn``;
    the flash kernel takes the turn's own output; no call fell back. The
    signed permutation is the kernels' operand (the MXU finds an entry's
    partner), so the ``[128, 128]`` constant is still in the text; the
    product with it is not: no dot stands under the ``rotary`` scope."""
    from mxtpu import telemetry
    from mxtpu.ops.registry import get_op
    attend = get_op("_contrib_grouped_attention").fn
    q = _spec((1, 16384, 72, 128), one_chip)
    k = _spec((1, 16384, 8, 128), one_chip)
    v = _spec((1, 16384, 8 * 128), one_chip)
    for name in ("calls", "pallas", "xla"):
        telemetry.reset_metric("rotary." + name)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v, window=512).astype(jnp.float32) ** 2)

    value = _compiled_text(lambda q, k, v: attend(q, k, v, window=512),
                           q, k, v)
    names = re.findall(r"%(rotary_\w+?)(?:\.\d+)? = .* custom-call\(", value)
    assert sorted(names) == ["rotary_turn", "rotary_turn"], names
    assert "flash_window_fwd" in value
    text = _compiled_text(jax.grad(loss, (0, 1, 2)), q, k, v)
    names = re.findall(r"%(rotary_\w+?)(?:\.\d+)? = .* custom-call\(", text)
    assert sorted(names) == ["rotary_turn", "rotary_turn", "rotary_unturn",
                             "rotary_unturn"], names
    for program in (value, text):
        assert not re.search(r"(convolution|dot)\(.*rotary/dot_general",
                             program)
        assert "f32[128,128]" not in program
    # q heads first is the turn's own output, written where its operand
    # stood: no array of q's size stands between it and the flash kernel
    turn = re.search(r"%rotary_turn[.\d]* = bf16\[1,72,16384,128\].*", value)
    assert turn and "output_to_operand_aliasing" in turn.group(0)
    assert [telemetry.value("rotary." + n)
            for n in ("calls", "pallas", "xla")] == [4, 4, 0]


def test_gdn_kernels_compile_to_mosaic(one_chip, as_tpu):
    """Both Gated-DeltaNet kernels at the qwen3_next cell's shapes (one
    layer, 16,384 positions, 32 value heads over 16 key heads of 128, bf16
    with a float32 decay a HEAD) as one differentiated program: Mosaic
    calls under their own names, q and k entering at their own 16 heads
    (fetched at head ``j // 2`` by the index maps: no copy at 32), a key
    head's cotangents leaving a value head each in float32 to be summed,
    and nothing a token kept: the states at the 256 chunk starts, 537 MB,
    and those two float32 arrays are the program's temporaries. The traces
    one pair reports leave the event ring its head at the cell's three
    layers under recomputation."""
    from mxtpu import telemetry
    kda = importlib.import_module("mxtpu.ops.pallas.kda")
    t = 16384
    qk, v = _spec((1, t, 2048), one_chip), _spec((1, t, 4096), one_chip)
    g = _spec((1, t, 32), one_chip, jnp.float32)
    beta = _spec((1, t, 32), one_chip)

    def loss(*a):
        return kda.gated_delta_rule(*a, 16, 64).astype(jnp.float32).sum()

    for name in ("calls", "fallbacks"):
        telemetry.reset_metric("gated_delta." + name)
    t0_us = time.perf_counter_ns() // 1000
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, v, g, beta).compile()
    text = compiled.as_text()
    traces = sum(1 for n, _c, ts, _d, _t in telemetry.events()
                 if n == "jax.trace" and ts >= t0_us)
    assert 0 < 3 * 2 * traces < telemetry.EVENT_RING_CAP // 2, traces
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "gdn_fwd" in text and "gdn_bwd" in text
    assert "kda_fwd" not in text and "kda_bwd" not in text
    assert [telemetry.value("gated_delta." + n)
            for n in ("calls", "fallbacks")] == [1, 0]
    # a state a chunk and value head, never a state a token; q and k never
    # at the value heads in bf16
    assert "f32[32,256,128,128]" in text
    assert "f32[32,16384,128,128]" not in text
    assert "f32[1,16384,4096]" in text          # dq, dk a value head
    temps = compiled.memory_analysis().temp_size_in_bytes
    states, partials = 32 * 256 * 128 * 128 * 4, 2 * t * 4096 * 4
    assert states + partials <= temps < states + partials + 0.3e9, temps
    # the forward alone keeps nothing
    alone = jax.jit(lambda *a: kda.gated_delta_rule(*a, 16, 64)).lower(
        qk, qk, v, g, beta).compile()
    assert alone.memory_analysis().temp_size_in_bytes < 1 << 20


def test_gated_attention_at_width_256_compiles_to_mosaic(one_chip, as_tpu):
    """``grouped_attention`` at the qwen3_next cell's shape (16,384
    positions, 16 query heads over 2 key/value heads of 256, rotary over a
    head's first 64 entries, bf16), differentiated: both flash kernels and
    both rotary kernels, no fallback. dk and dv of a whole key/value head
    (67 MB beside dq's 34) do not fit the backward's VMEM, so they leave a
    query head each in float32 (``pallas_flash.bwd_kv_by_query_head``) and
    XLA sums the eight; K and V are never repeated."""
    from mxtpu import telemetry
    from mxtpu.ops.registry import get_op
    attend = get_op("_contrib_grouped_attention").fn
    q = _spec((1, 16384, 16, 256), one_chip)
    k = _spec((1, 16384, 2, 256), one_chip)
    v = _spec((1, 16384, 2 * 256), one_chip)
    for name in ("rotary.calls", "rotary.pallas", "rotary.xla",
                 "pallas_flash.bwd_kv_by_query_head"):
        telemetry.reset_metric(name)
    fa.reset_dispatch_stats()

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v, rope_theta=1e7,
                              rotary_dim=64).astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, (0, 1, 2)), q, k, v)
    names = re.findall(r"%(rotary_\w+?|flash_\w+?)(?:\.\d+)? = .* "
                       r"custom-call\(", text)
    assert sorted(names) == ["flash_attention_bwd", "flash_attention_fwd",
                             "rotary_turn", "rotary_turn", "rotary_unturn",
                             "rotary_unturn"], names
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1), stats
    assert stats["xla"] == 0 and stats["bwd_xla"] == 0, stats
    assert stats["grouped"] == 1 and stats["kv_repeated"] == 0, stats
    assert telemetry.value("pallas_flash.bwd_kv_by_query_head") == 1
    assert [telemetry.value("rotary." + n)
            for n in ("calls", "pallas", "xla")] == [2, 2, 0]
    assert "f32[16,16384,256]" in text          # dk, dv a query head
    assert "bf16[1,16,16384,256]{3,2,1,0} broadcast" not in text
    assert "f32[16384,16384]" not in text
