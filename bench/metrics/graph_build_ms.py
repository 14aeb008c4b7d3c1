"""Engine: median, over searched answers, of the ``deploy.graph`` stage (the
partition lowered to its logical graph, inside the partition stage), in ms.
None where no answer carries it: a program that does not report it."""
from bench.phases import median_ms


def read(run):
    return median_ms(st["graph"] for st in run.searched_stage_times()
                     if "graph" in st)
