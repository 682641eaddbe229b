"""Host milliseconds in the gateway and its planner (harness spans
``gateway``: ``ServingGateway.offer``, the window close ``sync`` and the
plan read-out) per request offered in the traced stretch."""


def read(run):
    spans = run.rec.named("gateway", run.t0, run.t1)
    offered = sum(s.attrs["offered"] for s in spans)
    if not offered:
        return None
    return sum(s.seconds for s in spans) * 1e3 / offered
