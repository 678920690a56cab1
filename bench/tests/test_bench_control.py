"""The control, the reference computed in the precision below the one
its configuration states (bfloat16 for the float32 VGG) and put in the
program's place, fails the check of every cell, at a tiny size on the
CPU (on the chip it is read at the cells' own size by
``bench/readings.py``)."""
import pytest

import compare
import readings
import tiny


@pytest.mark.parametrize("name", tiny.NAMES)
def test_control_is_not_correct(name):
    cell = tiny.tiny_cell(name)
    r = readings.readings(cell, 7, "control")
    failed = [k for k, v in cell.limits.items()
              if not r["numbers"][k] <= v]
    assert failed, r["numbers"]


def test_the_program_reads_under_the_limits():
    cell = tiny.tiny_cell("vgg16bn-mod.bkfac")
    r = readings.readings(cell, 7, "program")
    assert all(r["numbers"][k] <= v for k, v in cell.limits.items()), r
    assert set(r["numbers"]) == set(compare.NUMBERS)


def test_a_nudged_reference_reads_under_the_limits():
    """The reference with its weights nudged by a rounding-sized step
    reads under every limit, and says where it parts from the reference
    at each checked step."""
    cell = tiny.tiny_cell("vgg16bn-mod.bkfac")
    r = readings.readings(cell, 7, "nudge")
    assert all(r["numbers"][k] <= v for k, v in cell.limits.items()), r
    assert len(r["parting"]["steps"]) == cell.job["check_steps"]


@pytest.mark.parametrize("kind", ["half", "label", "stale"])
def test_the_planted_faults_are_not_correct(kind):
    cell = tiny.tiny_cell("vgg16bn-mod.bkfac")
    r = readings.readings(cell, 7, kind)
    assert not r["correct_under_limits"], r["numbers"]
