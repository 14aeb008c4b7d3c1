"""Set-up: process start to the window's start (jax and chip start-up, the
compile cache, the server, every warm-up and any donor request)."""


def read(run):
    return run.setup_s
