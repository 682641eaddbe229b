"""Device busy seconds per pipeline-equivalent: the union of device-op
intervals over the traced window (every device operation of this cell is a
DC-placed DS operator or its input's transfer), over the pipeline-
equivalents whose task spans ended in that stretch. It reads no per-task
attribution, so it holds whether or not the executor waits for each task."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    done = sum(s.attrs["share"] for s in run.rec.named("task", run.t0, run.t1)
               if s.attrs["round"] >= 0)
    if not done:
        return None
    return run.trace.busy_s() / done
