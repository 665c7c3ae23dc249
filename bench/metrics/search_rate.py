"""(candidate, root vertex) pairs settled in the window per second.

A pair is settled when its root's block has been matched for that
candidate, or when the candidate's outcome is final without it (τ reached
early, or pruned).  Counted from the program's per-block record, never
from a timer; divided by the whole window."""


def read(run):
    if not run.queries or run.cell.traffic["loop"] != "cut":
        return None
    return sum(q.pairs for q in run.queries) / run.window_s
