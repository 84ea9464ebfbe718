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
import hashlib
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# recorded at PR 43 on both trees (the parent's, PR 42's, and the change's:
# the same eight); ResNet's and BERT's are the ones PR 42 recorded
RECORDED = {
    "resnet50_v1.train_b128":
        "4d324d72e998330d43a5d274780dadd27311abd272a228cc7b853dc18155a2f3",
    "bert_base.train_b16_s512":
        "5ad2020ef74b07df4e3028b3fbd00048ba31483ab93f3deb219e56f4cabeb0d6",
    "kanana2_30b_a3b.train_b1_s8192":
        "2dcb6a11a9364caa099bde42c68fbe056a87bc41959a44123f7b123f35626361",
    "lfm2_8b_a1b.train_b2_s8192":
        "22ed49a71549d9551a2424e6ea0e66f4f15df447baf8de8f91fa7aca17720e23",
    "smallthinker_21b_a3b.train_b1_s16384":
        "57dd39bcefcb71d5a3c441462446db5dd3a17bd070e7cb7919818dd9c5e5dac3",
    "resnet50_v1.train_dp4_b512":
        "4ee981a0809c3ec0e498ab322750ae81d14e71192e8ca07bfe3ccfc735b0b752",
    "keye_vl2_30b_a3b.train_b1_s16384":
        "d47ba0a9122a75e39b2809266ac7ea1b2201440e875d621f0d2530dff1db963a",
    # the two cells whose blocks are recomputed, recorded at PR 46: their
    # checkpoints keep what the kernels' forwards name
    # (``hybrid_lm.kept_policy``), so the backward's second forward lost its
    # attention and KDA calls. The seven above build no checkpoint and are
    # PR 45's still
    "ling3_flash.train_b1_s8192":
        "e95aeb1ed8abece6bdb0fd5c76dd36e65f6baa358d0e8a1a303031e89b5cf796",
    "laguna_s_2_1.train_b1_s16384":
        "4e2210359e614e0ef53439995fd46efe179bd81018e3521ae1cdc23bcc4707be",
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


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_a_cells_step_lowers_to_the_recorded_text(name, capsys, monkeypatch):
    import jax
    from benchmark import run
    from benchmark.models import common
    from mxtpu import telemetry
    from mxtpu.parallel import data_parallel_mesh
    # the tier-1 run has eight devices on the host: the program's mesh is
    # the cell's chips, as the reference's is (``train_steps._devices``)
    chips = run.Cell(name, rehearse=True).chips
    monkeypatch.setattr(common, "data_parallel_mesh",
                        lambda: data_parallel_mesh(jax.devices()[:chips]))
    for counter in ("kda_conv.calls", "kda_conv.pallas"):
        telemetry.reset_metric(counter)
    text = _tool().step_text(name)
    capsys.readouterr()             # the models' notes are the benchmark's
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == RECORDED[name], (
        "the lowered step of %s changed (%d characters, %s): if this PR "
        "means to change that cell's program, record the new hash here"
        % (name, len(text), got))
    # only the two delta-rule cells filter at all, and off the TPU never on
    # a kernel
    filtered = telemetry.value("kda_conv.calls")
    assert (filtered > 0) == name.startswith(("ling3_flash", "qwen3_next"))
    assert telemetry.value("kda_conv.pallas") == 0 and "kda_conv_" not in text
