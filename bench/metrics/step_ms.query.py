"""Device time of the batched match step programs over their count, ms."""
from bench import trace

STEP = "jit_step"


def read(run):
    if run.trace is None:
        return None
    ns, count = trace.module_time(run.trace, STEP)
    return ns / count / 1e6 if count else None
