"""Share of the chip's peak FLOP/s that the whole pipeline's device work
reached over the traced window: the operations of every DC-placed task
(``work.ds_task_work``) over window seconds times the peak."""


def read(run):
    if run.trace is None or not run.peaks:
        return None
    tasks = [s for s in run.rec.named("task", run.t0, run.t1)
             if s.attrs["backend"] == "device"]
    window = run.trace.window_s()
    if not tasks or window <= 0:
        return None
    flops = sum(s.attrs["flops"] for s in tasks)
    return 100.0 * flops / (window * run.peaks["flops_per_s"])
