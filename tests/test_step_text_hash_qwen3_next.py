"""``tests/test_step_text_hash.py``'s case for the cell PR 48 added, in a
file of its own (the tier-1 run hands out work by file and that file holds
nine set-ups already): the qwen3_next_80b_a3b cell's train step, lowered
on the CPU at its rehearsal sizes, is the program that was recorded. The
nine older cells' hashes did not move in PR 48, ling3's among them though
the two delta rules now share ``ops/pallas/kda.py``'s chunk functions."""
import test_step_text_hash as base

CELL = "qwen3_next_80b_a3b.train_b1_s16384"
RECORDED = "d585d8de38b311aec6d1a8d638b292bcaa3da2dd0a1a23aff1d1a5ad16de41e3"


def test_the_cells_step_lowers_to_the_recorded_text(capsys, monkeypatch):
    monkeypatch.setitem(base.RECORDED, CELL, RECORDED)
    base.test_a_cells_step_lowers_to_the_recorded_text(CELL, capsys,
                                                       monkeypatch)
