"""Engine: median, over searched answers, of the profile + partition +
schedule stage times the engine reports, in ms."""
from bench.stats import quantile


def read(run):
    d = [sum(st[k] for k in ("profile", "partition", "schedule")) * 1e3
         for st in run.searched_stage_times()]
    return quantile(d, 50) if d else None
