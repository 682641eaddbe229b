"""Share of the roofline reached by the DC-placed DS operators: the least
time their work needs on this chip (``work.ds_task_work``: each operand read
once, each result written once, the algorithm's arithmetic) for the device
tasks dispatched in the traced stretch, over the device busy time of that
stretch."""

import work


def read(run):
    if run.trace is None or not run.peaks:
        return None
    tasks = [s for s in run.rec.named("task", run.t0, run.t1)
             if s.attrs["backend"] == "device" and s.attrs["round"] >= 0]
    busy = run.trace.busy_s()
    if not tasks or busy <= 0:
        return None
    need = sum(work.min_time(s.attrs["flops"], s.attrs["bytes"], run.peaks)
               for s in tasks)
    return 100.0 * need / busy
