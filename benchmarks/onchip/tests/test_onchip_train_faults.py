"""The train cells' correctness check, driven end to end on the CPU at a
tiny size: each fault a training cell can have on one chip makes a run
incorrect, and the float8 control fails the limits."""
from __future__ import annotations

import time

import pytest

import tiny
import harness


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return tiny.tiny_cell(tmp_path_factory.mktemp("tiny"), "gpt2-train-off")


def _run(cell, fault=None, control=False):
    drv = harness.load_driver(cell.driver)
    return drv.run(cell, 2 ** 31 + 77, 1.0, time.perf_counter(),
                   fault=fault, control=control)


def test_the_control_fails_the_limits(cell):
    """The float8 control, put in the program's place, fills the compared
    numbers and the run comes out not correct; the sound program, read in
    the same run, reads below the control on every number."""
    res = _run(cell, control=True)
    assert res.attempted > 0 and res.failed == 0
    assert set(res.end_to_end) == {"setup_s", "tokens_per_s"}
    assert not res.correct
    prog = res.info["program"]
    assert all(prog[c.name] < c.value for c in res.checks)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(cell, fault):
    res = _run(cell, fault=fault)
    assert not res.correct
