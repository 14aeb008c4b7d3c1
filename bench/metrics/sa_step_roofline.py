"""Device SA step: least time for the bytes one step has to move at the
chip's HBM peak, over the measured step time, in %. The bytes are counted
from the algorithm's work (``bench.work.sa_step_bytes``), so the share is
the same whichever implementation runs the step."""
from bench.work import sa_step_bytes


def read(run):
    t = run.sa_step_s()
    if t is None:
        return None
    least = sa_step_bytes(run.incident_degree(), run.chains()) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
