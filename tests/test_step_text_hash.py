"""Each cell's train step, lowered on the CPU at the cell's rehearsal
sizes, is the program it was: the sha256 of the lowered text
(``tools/step_text_hash.py``) against the one recorded here. A PR that is
meant to leave a cell alone (a kernel for another cell's operator, say)
sees so before any chip time; a PR that changes a cell's program on
purpose records the new hash in the same commit:

    JAX_PLATFORMS=cpu python tools/step_text_hash.py [cell ...]

(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for the four-chip
cell). Off the TPU an operator's Pallas kernels are refused (``platform``)
and the plain path is lowered, so a kernel PR moves none of these, its own
cell's included; what it lowers for the chip is ``test_tpu_compile.py``'s.
A file of its own: a case is a whole rehearsal set-up, and the tier-1 run
hands out work by file."""
import functools
import hashlib
import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# recorded at PR 49 on both trees, of the text with its private functions
# counted anew (``step_text_hash.renumbered``): the parent's (PR 48's) and
# the change's give the same seven below, which build no checkpoint. Their
# raw texts differ in those numbers alone (``@_where_138`` ->
# ``@_where_140``): the routed layer now names what its router decided
# (``moe.KEPT_NAMES``), a name lowers to nothing, and MLIR's counter counts
# it all the same
RECORDED = {
    "resnet50_v1.train_b128":
        "124a0b1288c7adc099f18606ce942ef5740d6a9e74a62067ab0bd6b2715ba07a",
    "bert_base.train_b16_s512":
        "94067e0637717ed0172e52c6358ad0368d9f7e4a9a752a9194dba44e3e798535",
    "kanana2_30b_a3b.train_b1_s8192":
        "9f70b4d6ed5f5cf25b51b17472abc85ba560f230d72ce3096152e5e6d8282d7b",
    "lfm2_8b_a1b.train_b2_s8192":
        "34aa02d9f08978bc70cb192f28cfbe7d2fa6fc285d9b98295c7dde69dc5f979d",
    "smallthinker_21b_a3b.train_b1_s16384":
        "63aa10c43bb21e1fc4dab6039f0a5e4e93ab878bced385562a2017e45f60354f",
    "resnet50_v1.train_dp4_b512":
        "a730bc03dd6dea4dc7b9c52b32319b1137610477a4fe72064af8a879af13088a",
    "keye_vl2_30b_a3b.train_b1_s16384":
        "18e110a421e37ed353e547a7272622b9173e0baf62f3828bc8951c2d26a64ddd",
    # the cells whose blocks are recomputed (qwen3_next's in its own file),
    # which MOVED in PR 49: their checkpoints keep what the kernels'
    # forwards and the routers name (``hybrid_lm.kept_policy``), so the
    # backward's second forward lost its attention and KDA calls (PR 46) and
    # its router products, ``top_k``s and sorts (PR 49). The parent's read
    # d83b666d... and 6d1f5d85... under the same renumbering
    "ling3_flash.train_b1_s8192":
        "884fc13007d58338c2aae9eeaa1c3b41e31e9e0a4bad1c744190202de85ce351",
    "laguna_s_2_1.train_b1_s16384":
        "5b84279d81fad8b0c402cca346ff545118672ec4de89d47094e47f887502a972",
    # ``qwen3_next_80b_a3b.train_b1_s16384`` (PR 48) is recorded in a file
    # of its own, ``tests/test_step_text_hash_qwen3_next.py``: a case is a
    # whole rehearsal set-up, and the run cannot end before its longest file
}


def _tool():
    spec = importlib.util.spec_from_file_location(
        "step_text_hash", os.path.join(ROOT, "tools", "step_text_hash.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _built(name):
    """One rehearsal set-up a cell for both tests below: the step's lowered
    text, the operation table of the executable it ran
    (``xprof.step_operations``), its blocks' paths and the kernel counters
    its trace moved; the step itself is freed."""
    from mxtpu import telemetry, xprof
    for counter in ("kda_conv.calls", "kda_conv.pallas"):
        telemetry.reset_metric(counter)
    step, net = _tool().step_of(name)
    counted = (telemetry.value("kda_conv.calls"),
               telemetry.value("kda_conv.pallas"))
    events = len(telemetry.events())
    handle = step.compiled()
    table = xprof.step_operations()
    # reading the executable and its table traced and lowered nothing
    assert [e[0] for e in telemetry.events()[events:]] == []
    assert handle.as_text().startswith("HloModule jit_sharded_train_step")
    text = _tool().step_text(name, step)
    return text, table, _paths(net), bool(getattr(net, "_recompute", 0)), \
        counted


def _paths(block, above=()):
    """The path of every block under ``block`` that holds parameters of its
    own, as ``Block.__call__`` names it: own names from the top's children
    down."""
    found = []
    for child in block._children.values():
        path = above + (child._own_name,)
        if child._reg_params:
            found.append("/".join(path))
        found += _paths(child, path)
    return found


def _cell(name, monkeypatch):
    import jax
    from benchmark import run
    from benchmark.models import common
    from mxtpu.parallel import data_parallel_mesh
    # the tier-1 run has eight devices on the host: the program's mesh is
    # the cell's chips, as the reference's is (``train_steps._devices``)
    chips = run.Cell(name, rehearse=True).chips
    monkeypatch.setattr(common, "data_parallel_mesh",
                        lambda: data_parallel_mesh(jax.devices()[:chips]))
    return _built(name)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_a_cells_step_lowers_to_the_recorded_text(name, capsys, monkeypatch):
    text, _table, _blocks, _recomputes, counted = _cell(name, monkeypatch)
    capsys.readouterr()             # the models' notes are the benchmark's
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == RECORDED[name], (
        "the lowered step of %s changed (%d characters, %s): if this PR "
        "means to change that cell's program, record the new hash here"
        % (name, len(text), got))
    # only the two delta-rule cells filter at all, and off the TPU never on
    # a kernel
    assert (counted[0] > 0) == name.startswith(("ling3_flash", "qwen3_next"))
    assert counted[1] == 0 and "kda_conv_" not in text


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_a_cells_table_holds_every_blocks_path(name, capsys, monkeypatch):
    """The executable the cell's step ran names its operations by the
    model's own path (``Block.__call__``): every block that holds
    parameters is found under the forward and under the backward
    (``transpose(``), and where the blocks are recomputed every layer under
    ``rematted_computation`` too."""
    from benchmark import step_scopes
    from mxtpu import xprof
    _text, table, blocks, recomputes, _counted = _cell(name, monkeypatch)
    capsys.readouterr()
    assert len(blocks) > 10
    seen = {"forward": set(), "recomputed": set(), "backward": set()}
    for row in table.values():
        transform = xprof.transform_of(row["op_name"])
        if transform in seen:
            seen[transform].add(tuple(step_scopes.scopes_of(
                xprof.scope_path(row["op_name"]))))

    def within(block, scopes):      # in order; operators' scopes between
        rest = iter(scopes)
        return all(part in rest for part in block.split("/"))

    for transform in ("forward", "backward"):
        # (an indexer picks keys: a choice, which takes no gradient)
        lost = [b for b in blocks
                if not any(within(b, path) for path in seen[transform])
                and not (transform == "backward" and b.endswith("/indexer_"))]
        assert not lost, (transform, lost)
    assert bool(seen["recomputed"]) == recomputes
    if recomputes:
        layers = {step_scopes.kind_of(b.split("/") + ["x"])[1]
                  for b in blocks if b.startswith("h_/")}
        again = {step_scopes.kind_of(list(path) + ["x"])[1]
                 for path in seen["recomputed"]}
        assert len(layers) > 1 and again == layers


@pytest.mark.parametrize("text,same", [
    # two lowerings more ahead of them move MLIR's numbers and nothing else
    ("call @_where_140(%1) call @_where_152(%2) call @_where_140(%3) "
     "func @_where_140 func @_where_152 call @clip(%4)", True),
    # another function called at a site is another program
    ("call @_where_138(%1) call @_where_148(%2) call @_where_148(%3) "
     "func @_where_138 func @_where_148 call @clip(%4)", False),
], ids=["renumbered", "another-callee"])
def test_private_functions_are_counted_anew(text, same):
    was = ("call @_where_138(%1) call @_where_148(%2) call @_where_138(%3) "
           "func @_where_138 func @_where_148 call @clip(%4)")
    renumbered = _tool().renumbered
    assert renumbered(was) == (
        "call @_where_1(%1) call @_where_2(%2) call @_where_1(%3) "
        "func @_where_1 func @_where_2 call @clip(%4)")
    assert (renumbered(text) == renumbered(was)) == same
