"""Device milliseconds per batched decode step: the duration of every
``jit_decode_step`` program run in the traced window over their count."""


def read(run):
    if run.trace is None:
        return None
    ev = run.trace.module_events("jit_decode_step")
    if not ev:
        return None
    return sum(e - b for _n, b, e in ev) * 1e-6 / len(ev)
