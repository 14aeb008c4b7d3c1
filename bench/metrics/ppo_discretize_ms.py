"""Search: median, over searched answers, of the request's time in the
``ppo.discretize`` phase: host discretization of the actions into
placements, summed over the PPO iterations, in ms."""
from bench.phases import search_phase_ms


def read(run):
    return search_phase_ms(run, "ppo.discretize")
