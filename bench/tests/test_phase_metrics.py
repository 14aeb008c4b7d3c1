"""The readers of the served path's per-request times, on hand-made runs:
each reads the median of what the answers carry, and nothing where the
program does not report the field."""
import importlib.util
from pathlib import Path

import pytest

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    def __init__(self, responses):
        self.records = [{"response": r, "in_window": True}
                        for r in responses]

    def window_records(self):
        return self.records


def answer(status="miss", queue_s=0.0105, **phases):
    return {"status": status, "queue_s": queue_s,
            "report": {"search_phases_s": phases}}


def test_queue_wait_ms():
    run = Run([answer(queue_s=0.010), answer("hit", queue_s=0.012),
               answer(queue_s=0.011), None])
    assert reader("queue_wait_ms")(run) == pytest.approx(11.0)


@pytest.mark.parametrize("name,phase", [
    ("sa_prepare_ms", "sa.prepare"),
    ("ppo_sample_ms", "ppo.sample"),
    ("ppo_discretize_ms", "ppo.discretize"),
    ("ppo_score_ms", "ppo.score"),
    ("ppo_update_ms", "ppo.update"),
])
def test_search_phase_ms(name, phase):
    other = "ppo.score" if phase != "ppo.score" else "ppo.sample"
    run = Run([answer(**{phase: 0.2, other: 9.0}),
               answer(**{phase: 0.4}),
               answer(**{phase: 0.3}),
               answer("hit", **{phase: 5.0}),   # a hit searched nothing
               answer(**{other: 1.0}),          # another method's phases
               None])
    assert reader(name)(run) == pytest.approx(300.0)


@pytest.mark.parametrize("name", ["queue_wait_ms", "sa_prepare_ms",
                                  "ppo_sample_ms", "ppo_discretize_ms",
                                  "ppo_score_ms", "ppo_update_ms"])
def test_nothing_read_from_a_program_without_the_fields(name):
    run = Run([{"status": "miss", "latency_s": 0.3,
                "report": {"stage_times_s": {"place": 0.28}}}])
    assert reader(name)(run) is None
