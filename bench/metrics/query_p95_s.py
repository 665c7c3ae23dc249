"""95th percentile of the latencies of all the window's queries."""
import numpy as np


def read(run):
    if not run.queries or run.cell.traffic["loop"] != "closed":
        return None
    return float(np.percentile([q.latency_s for q in run.queries], 95))
