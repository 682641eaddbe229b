"""Share of the traced window in which no operation ran on the device
(DS pipeline cells): 1 - union of device-op intervals / window."""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
