"""A whole run of each cell with the timed path broken underneath, on the
CPU (the harness's look for a chip skipped): ``correct`` must come out
false for each fault the cell can have, and for the control. A sound run
comes out true."""
import argparse
import contextlib
from unittest import mock

import numpy as np
import pytest

from bench import control
from bench import run as bench_run

CELLS = ["sresnet50-mesh8x8-devsa.cold", "sresnet18-mesh4x8-ppo.cold"]


@contextlib.contextmanager
def altered_answer():
    """The search's answer altered where it is produced: one logical core
    mapped onto the same physical core as another."""
    import repro.core.placement as placement

    optimize = placement.optimize_placement

    def altered(*args, **kw):
        res = optimize(*args, **kw)
        p = np.array(res.placement)
        p[0] = p[1]
        res.placement = p
        return res
    with mock.patch.object(placement, "optimize_placement", altered):
        yield


def run_cell(cell, seed=2 ** 31 + 7, seconds=2.0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    return bench_run.run_cell(args, require_chip=False)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run_cell(cell)
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [control.frozen_search, altered_answer,
                                   control.control_in_place])
def test_fault_is_caught(cell, fault):
    with fault():
        out = run_cell(cell)
    assert not out["correct"], out["checks"]

