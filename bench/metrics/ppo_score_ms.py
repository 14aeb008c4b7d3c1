"""Search: median, over searched answers, of the request's time in the
``ppo.score`` phase: host float64 scoring of the rollouts, summed
over the PPO iterations, in ms."""
from bench.phases import search_phase_ms


def read(run):
    return search_phase_ms(run, "ppo.score")
