"""Share of the roofline reached by the decode step: the least time of the
traced stretch's decode steps (``work.decode_step_work``: the weights once,
each active slot's valid K/V once, two flops per weight and token plus
attention over the valid positions) over their device time
(``jit_decode_step``)."""

import work


def read(run):
    if run.trace is None or not run.peaks:
        return None
    ev = run.trace.module_events("jit_decode_step")
    ticks = run.rec.named("tick", run.t0, run.t1)
    if not ev or not ticks:
        return None
    m = run.layer["model"]
    need = sum(work.min_time(*work.decode_step_work(m, s.attrs["kv"]), run.peaks)
               for s in ticks if s.attrs["kv"])
    return 100.0 * need / (sum(e - b for _n, b, e in ev) * 1e-9)
