"""HTTP and micro-batch queue: median, over the window's answers, of the
time each request waited in the service's micro-batch queue (the answer's
``queue_s``, measured by the queue at the hand-off of its batch), in ms."""
from bench.phases import median_ms


def read(run):
    return median_ms(r["response"]["queue_s"] for r in run.window_records()
                     if r["response"] is not None
                     and "queue_s" in r["response"])
