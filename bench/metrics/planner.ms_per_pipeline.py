"""Host milliseconds in the planner (``OnlineDriver.submit`` and the
placement loop, harness spans ``planner``) per pipeline-equivalent completed
in the traced stretch."""


def read(run):
    done = sum(s.attrs["share"] for s in run.rec.named("task", run.t0, run.t1)
               if s.attrs["round"] >= 0)
    spans = run.rec.named("planner", run.t0, run.t1)
    if not done or not spans:
        return None
    return sum(s.seconds for s in spans) * 1e3 / done
