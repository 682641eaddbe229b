"""Device milliseconds per admitted request spent on admission: inside each
engine tick that admitted a request, the device busy time other than the
decode step (``jit_decode_step``): the prefill program, the fresh cache, the
first token's argmax and the slot insert."""


def read(run):
    if run.trace is None:
        return None
    ticks = [s for s in run.trace.named("tick") if int(s.args.get("admitted", 0))]
    if not ticks:
        return None
    total = 0.0
    for s in ticks:
        dec = sum(e - b for _n, b, e in
                  run.trace.module_events("jit_decode_step", s.t0, s.t1))
        total += run.trace.busy_ns(s.t0, s.t1) - dec
    return total * 1e-6 / len(ticks)
