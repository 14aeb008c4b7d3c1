"""Plans answered in the window over the window's length (first request
sent to last completion, on the client's clock)."""


def read(run):
    done = sum(1 for r in run.window_records() if r["response"] is not None)
    return done / run.window_s if run.window_s > 0 else None
