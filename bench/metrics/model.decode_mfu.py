"""Model FLOP/s utilisation of the whole decode step: the model's operations
for the traced stretch's decode steps (two per weight per active token, plus
attention over the valid positions) over their device time times the chip's
peak."""

import work


def read(run):
    if run.trace is None or not run.peaks:
        return None
    ev = run.trace.module_events("jit_decode_step")
    ticks = run.rec.named("tick", run.t0, run.t1)
    if not ev or not ticks:
        return None
    m = run.layer["model"]
    flops = sum(work.decode_step_work(m, s.attrs["kv"])[0]
                for s in ticks if s.attrs["kv"])
    busy = sum(e - b for _n, b, e in ev) * 1e-9
    return 100.0 * flops / (busy * run.peaks["flops_per_s"])
