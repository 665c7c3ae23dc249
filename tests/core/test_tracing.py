"""The query record of `core/tracing.py`: the span tree `mine()` returns,
its counters, the profiler's clock, and answers unchanged by the record."""
import glob
import hashlib
import os

import pytest

from repro.core import MatchConfig, MiningConfig, mine, tracing
from repro.data.synthetic import rmat_graph

# The stats digest of each plane's answer to `_query`, as the miner gave it
# before it kept a record: the record must change no answer.
DIGESTS = {"batched": "f1176717d1ea1dab", "sequential": "f1176717d1ea1dab",
           "sampled": "f1176717d1ea1dab"}


def _query(execution="batched", max_size=3):
    g = rmat_graph(120, 480, n_labels=3, seed=3, undirected=True)
    return g, MiningConfig(
        sigma=8, lam=0.5, metric="mis", max_pattern_size=max_size,
        execution=execution, sample_fraction=0.5,
        match=MatchConfig.for_graph(g, cap=1024, root_block=32, chunk=4))


def _digest(res):
    rows = sorted((st.pattern.key(), st.support, st.frequent,
                   st.embeddings_found, st.overflowed, st.blocks_run)
                  for st in res.stats)
    return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def batched_twice():
    g, cfg = _query()
    return mine(g, cfg), mine(g, cfg)


def _ancestors(trace, i):
    out = []
    while trace.spans[i].parent >= 0:
        i = trace.spans[i].parent
        out.append(trace.spans[i].name)
    return out


def test_one_tree_rooted_at_mine(batched_twice):
    trace = batched_twice[0].trace
    spans = trace.spans
    assert spans[0].name == "mine" and spans[0].parent == -1
    assert [s.parent for s in spans].count(-1) == 1
    for i, s in enumerate(spans):
        assert s.parent < i
        assert s.start_ns <= s.end_ns
        if s.parent >= 0:
            p = spans[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
    blocks = [i for i, s in enumerate(spans) if s.name == "block"]
    assert blocks
    for i in blocks:
        assert "level" in _ancestors(trace, i)
    for name in ("setup", "cand_build", "level", "plan", "plan_build",
                 "dispatch", "pull", "account", "generate", "dedup"):
        assert trace.named(name), name
    res = batched_twice[0]
    assert [s.meta["level"] for s in trace.named("level")] \
        == sorted(res.per_level)
    for s in trace.named("level"):
        assert res.per_level[s.meta["level"]]["wall_s"] == s.seconds
    assert res.elapsed_s == spans[0].seconds


def test_self_seconds_add_up_to_the_root(batched_twice):
    trace = batched_twice[0].trace
    self_s = trace.self_seconds()
    assert sum(self_s.values()) == pytest.approx(trace.spans[0].seconds)
    assert all(v >= -1e-9 for v in self_s.values())


def test_counters_are_deterministic_and_agree_with_the_result(batched_twice):
    first, second = batched_twice

    def counts(res):
        return {k: v for k, v in res.trace.counters.items()
                if k not in ("compiles", "compile_s")}

    assert counts(first) == counts(second)
    c = first.trace.counters
    dispatches = sum(v["dispatches"] for v in first.per_level.values())
    assert c["match_blocks"] + c.get("replay_blocks", 0) == dispatches
    assert len(first.trace.named("block")) == dispatches
    assert c["plans_built"] == first.searched + c.get("escalated", 0)
    assert c["host_pulls"] == 5 * c["match_blocks"]
    assert c["restacks"] == len(first.trace.named("restack"))
    assert 0 < c["lanes_useful"] < c["lanes_processed"]
    # the warm run loads every program it needs
    assert second.trace.counters.get("compiles", 0) == 0
    assert first.trace.counters["compiles"] > 0


def test_two_vertex_lanes_useful_are_the_embeddings_found():
    g, cfg = _query(max_size=2)
    res = mine(g, cfg)
    c = res.trace.counters
    assert c["lanes_useful"] == sum(st.embeddings_found for st in res.stats)
    assert c["lanes_processed"] % (cfg.match.chunk) == 0


@pytest.mark.parametrize("execution", sorted(DIGESTS))
def test_answers_unchanged_by_the_record(execution, batched_twice):
    if execution == "batched":
        res = batched_twice[0]
    else:
        res = mine(*_query(execution))
    assert _digest(res) == DIGESTS[execution]
    assert res.trace.spans[0].name == "mine"
    assert res.trace.counters["plans_built"] >= res.searched


@pytest.mark.parametrize("execution", sorted(DIGESTS))
def test_one_plan_put_per_candidate_group(execution, batched_twice,
                                          monkeypatch):
    """Each candidate group puts its stacked plan on the device once (each
    pattern its own plan, on the sequential plane): no path puts plan
    fields one by one.  Planning and answers are as before."""
    from repro.core import batched as batched_lib
    from repro.core import flexis as flexis_lib
    from repro.core import sampled as sampled_lib

    groups = []

    def counted(fn):
        def run(*args, **kwargs):
            groups.append(fn.__name__)
            return fn(*args, **kwargs)
        return run

    for module, name in ((batched_lib, "_mine_group"),
                         (sampled_lib, "_mine_group"),
                         (flexis_lib, "evaluate_pattern")):
        monkeypatch.setattr(module, name, counted(getattr(module, name)))
    res = mine(*_query(execution))
    c = res.trace.counters
    assert groups and c["plan_puts"] == len(groups)
    assert _digest(res) == DIGESTS[execution]
    if execution == "batched":
        warm = batched_twice[1].trace.counters
        assert c["plan_puts"] == warm["plan_puts"]
        assert c["plans_built"] == warm["plans_built"]
        # a third query in the process: still every program cached
        assert c.get("compiles", 0) == 0


def test_outside_a_query_spans_only_annotate():
    with tracing.span("block") as s:
        tracing.count("host_pulls")
    assert s.end_ns >= s.start_ns
    with tracing.query() as outer:
        with tracing.span("level"):
            with tracing.query() as inner:
                tracing.count("plans_built", 2)
            tracing.count("plans_built")
    assert inner.counters == {"plans_built": 2} and not inner.spans
    assert outer.counters == {"plans_built": 1}
    assert [s.name for s in outer.spans] == ["level"]


def test_spans_share_the_profilers_clock(tmp_path):
    """The record's `mine` span and its annotation in the profiler's trace
    agree within 100 µs at both ends, once the trace's origin is added."""
    from jax.profiler import ProfileData
    import jax

    g, cfg = _query(max_size=2)
    mine(g, cfg)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = mine(g, cfg)
    path = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    data = ProfileData.from_file(path)
    origin = next(int(v) for p in data.planes for k, v in p.stats
                  if k == "profile_start_time")
    twins = [(e.start_ns, e.duration_ns) for p in data.planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name == "flexis.mine"]
    assert len(twins) == 1
    start, dur = twins[0]
    root = res.trace.spans[0]
    assert abs(origin + start - root.start_ns) < 100_000
    assert abs(origin + start + dur - root.end_ns) < 100_000
