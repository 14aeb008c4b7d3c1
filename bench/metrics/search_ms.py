"""Search: median, over searched answers, of the engine's place stage (it
ends once the plan is back on the host), in ms."""
from bench.stats import quantile


def read(run):
    d = [st["place"] * 1e3 for st in run.searched_stage_times()]
    return quantile(d, 50) if d else None
