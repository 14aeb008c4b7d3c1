"""Search: median, over searched answers, of the request's time in the
``sa.prepare`` phase: the device SA's host set-up (start
placements, incident tables, uploads), in ms."""
from bench.phases import search_phase_ms


def read(run):
    return search_phase_ms(run, "sa.prepare")
