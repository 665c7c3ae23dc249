"""The whole window over the queries completed in it (closed loop)."""


def read(run):
    if not run.queries or run.cell.traffic["loop"] != "closed":
        return None
    return run.window_s / len(run.queries)
