"""Host time of the level-1 candidate build (the program's
``initial_candidates``, timed from outside), ms per query."""


def read(run):
    times = run.spans.seconds.get("cand_build")
    if not run.spans.found.get("cand_build") or not times:
        return None
    return 1000.0 * sum(times) / len(times)
