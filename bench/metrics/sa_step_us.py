"""Device SA step: device time of the SA search programs that ran wholly
inside the traced window over the scan steps they ran, in us."""


def read(run):
    t = run.sa_step_s()
    return None if t is None else t * 1e6
