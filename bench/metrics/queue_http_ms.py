"""HTTP + micro-batch queue: median over requests of the client latency
minus the service's own ``latency_s`` for that request, in ms."""
from bench.stats import quantile


def read(run):
    d = [(r["t1"] - r["t0"] - r["response"]["latency_s"]) * 1e3
         for r in run.window_records() if r["response"] is not None]
    return quantile(d, 50) if d else None
