"""The reader of the engine's graph-lowering stage, on hand-made runs: the
median over searched answers, and nothing where the program does not
report the stage."""
import importlib.util
from pathlib import Path

import pytest

PATH = Path(__file__).resolve().parents[1] / "metrics" / "graph_build_ms.py"


def read(run):
    spec = importlib.util.spec_from_file_location("graph_build_ms", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class Run:
    def __init__(self, stage_times):
        self.stage_times = stage_times

    def searched_stage_times(self):
        return self.stage_times


def test_median_of_searched_answers():
    run = Run([{"partition": 0.04, "graph": 0.002},
               {"partition": 0.05, "graph": 0.004},
               {"partition": 0.03, "graph": 0.003}])
    assert read(run) == pytest.approx(3.0)


@pytest.mark.parametrize("stage_times", [
    [], [{"profile": 0.001, "partition": 0.04, "place": 0.3}]])
def test_nothing_read_without_the_stage(stage_times):
    assert read(Run(stage_times)) is None
