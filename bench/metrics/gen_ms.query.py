"""Host time per query in candidate generation and canonical dedup
(the program's ``generate_new_patterns``, timed from outside), ms."""


def read(run):
    times = run.spans.seconds.get("generation")
    if not run.spans.found.get("generation") or not times or not run.queries:
        return None
    return 1000.0 * sum(times) / len(run.queries)
