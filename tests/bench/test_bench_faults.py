"""Each fault a cell can have, planted in the timed path underneath a
whole run (the chip checks skipped), makes `correct` come out false; so
does the control, the reference in a precision below the configuration's
put in the program's place."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cell  # noqa: E402


def _wide(monkeypatch):
    """The wide tiny model, whose greedy tokens depend on the layers."""
    monkeypatch.setattr(tiny_cell, "TINY", tiny_cell.WIDE)
    monkeypatch.setitem(tiny_cell.MIXES, "tiny_closed",
                        tiny_cell.MIXES["wide_closed"])


def _plant(monkeypatch, fault):
    from bench import faults
    for obj, attr, value in faults.patches(fault):
        monkeypatch.setattr(obj, attr, value)


def test_altered_token_is_caught(tmp_path, monkeypatch):
    _plant(monkeypatch, "altered_token")
    out, _ = tiny_cell.run_tiny(tmp_path, "tiny_closed", seed=4)
    assert out["correct"] is False


def test_decode_state_left_unchanged_is_caught(tmp_path, monkeypatch):
    _plant(monkeypatch, "decode_unchanged")
    _wide(monkeypatch)
    out, _ = tiny_cell.run_tiny(tmp_path, "tiny_closed", seed=5)
    assert out["correct"] is False


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_faults_are_caught(tmp_path, monkeypatch, fault):
    _plant(monkeypatch, fault)
    out, checks = tiny_cell.run_tiny(tmp_path, "tiny_train", seed=8)
    assert out["correct"] is False


def test_half_of_one_row_is_caught(tmp_path, monkeypatch):
    """With a batch of one row, as the chip cell has, the fault drops half
    of its positions; the gradient norm before the clip sees it."""
    _plant(monkeypatch, "half_batch")
    monkeypatch.setitem(tiny_cell.MIXES, "tiny_train",
                        dict(tiny_cell.MIXES["tiny_train"], batch=1))
    out, checks = tiny_cell.run_tiny(tmp_path, "tiny_train", seed=8)
    assert out["correct"] is False
    assert checks["gnorm_gap"] > tiny_cell.LIMITS["train"]["gnorm_gap"]


def test_train_control_fails(tmp_path):
    out, checks = tiny_cell.run_tiny(tmp_path, "tiny_train", seed=9,
                                     control="fp8")
    assert out["correct"] is False


def test_serve_control_fails(tmp_path, monkeypatch):
    _wide(monkeypatch)
    out, checks = tiny_cell.run_tiny(tmp_path, "tiny_closed", seed=1,
                                     control="fp8")
    assert out["correct"] is False
    assert checks["logit_gap_max"] > tiny_cell.LIMITS["serve"][
        "logit_gap_max"]
