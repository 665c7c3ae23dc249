"""Device step dispatches per query, summed over its levels."""


def read(run):
    if not run.queries:
        return None
    return sum(sum(int(v.get("dispatches", 0)) for v in q.result.per_level.values())
               for q in run.queries) / len(run.queries)
