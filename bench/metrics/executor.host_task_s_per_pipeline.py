"""Host seconds in edge-placed tasks (numpy on the host, harness spans
``task`` with backend ``host``) per pipeline-equivalent completed in the
traced stretch."""


def read(run):
    tasks = [s for s in run.rec.named("task", run.t0, run.t1)
             if s.attrs["round"] >= 0]
    done = sum(s.attrs["share"] for s in tasks)
    host = [s for s in tasks if s.attrs["backend"] == "host"]
    if not done or not host:
        return None
    return sum(s.seconds for s in host) / done
