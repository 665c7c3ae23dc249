"""Host time per query in the program's ``plan_build`` spans: matching
plans, their stacking and the metric state of each candidate group, ms."""
from bench import program_spans


def read(run):
    recs = program_spans.records(run)
    if recs is None:
        return None
    return 1000.0 * sum(s.seconds for r in recs for s in r.named("plan_build")) \
        / len(recs)
