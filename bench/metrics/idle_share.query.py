"""Share of the traced window in which no operation ran on the device, %."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    idle = trace.idle_share(run.trace)
    return None if idle is None else 100.0 * idle
