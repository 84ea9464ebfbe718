"""``tests/test_step_text_hash.py``'s case for the cell PR 48 added, in a
file of its own (the tier-1 run hands out work by file and that file holds
nine set-ups already): the qwen3_next_80b_a3b cell's train step, lowered
on the CPU at its rehearsal sizes, is the program that was recorded: at
PR 49, whose checkpoints keep what the four routers decided (the parent's
text read 8a3abf89... under the same renumbering of private functions)."""
import test_step_text_hash as base

CELL = "qwen3_next_80b_a3b.train_b1_s16384"
RECORDED = "5ece84c252b395eee4065a9f2312be8870209e2f4524f61b3ad221ff270e2d9f"


def test_the_cells_step_lowers_to_the_recorded_text(capsys, monkeypatch):
    monkeypatch.setitem(base.RECORDED, CELL, RECORDED)
    base.test_a_cells_step_lowers_to_the_recorded_text(CELL, capsys,
                                                       monkeypatch)


def test_the_cells_table_holds_every_blocks_path(capsys, monkeypatch):
    base.test_a_cells_table_holds_every_blocks_path(CELL, capsys,
                                                    monkeypatch)
