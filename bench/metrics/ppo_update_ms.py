"""Search: median, over searched answers, of the request's time in the
``ppo.update`` phase: rewards and the update dispatch, up to the
losses on the host, summed over the PPO iterations, in ms."""
from bench.phases import search_phase_ms


def read(run):
    return search_phase_ms(run, "ppo.update")
