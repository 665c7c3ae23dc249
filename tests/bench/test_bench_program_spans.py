"""The readers of the program's own spans and counters: each query's
record placed on the trace's clock, device-idle time inside the program's
spans, and no number where the clocks disagree or no record is kept."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import program_spans, run as bench_run, trace
from repro.core.tracing import QueryTrace, Span

DATA = Path(__file__).parent / "data"
MS = 1_000_000
# the realtime clock's reading at the trace's origin
ORIGIN = 1_792_000_000 * 10**9

# Two queries in a 100 ms window; the device runs three operations.
# Idle: [0, 15] [25, 30] [35, 60] [80, 100] ms, 65 ms in all.
TRACE = {
    "devices": {"0": {"ops": [["fusion.1", 15 * MS, 10 * MS],
                              ["fusion.2", 30 * MS, 5 * MS],
                              ["fusion.1", 60 * MS, 20 * MS]],
                      "modules": []}},
    "spans": [["bench.window", 0, 100 * MS],
              ["bench.query", 50 * MS, 40 * MS],
              ["bench.query", 10 * MS, 30 * MS]],
}


def _record(spans, **counters):
    """A query record from (name, start ms, end ms, parent) on the trace's
    clock, moved onto the realtime clock."""
    return QueryTrace(
        spans=[Span(n, ORIGIN + round(a * MS), ORIGIN + round(b * MS), p, {})
               for n, a, b, p in spans],
        counters=dict(counters))


def _records(end_shift_ms=0.0):
    return [
        # mine sits 1 µs inside bench.query at each end
        _record([("mine", 10.001, 39.999, -1), ("level", 12, 38, 0),
                 ("block", 14, 26, 1), ("dispatch", 14, 15, 2),
                 ("pull", 15, 25, 2), ("block", 26, 36, 1),
                 ("plan_build", 36, 37, 1)],
                compiles=2, lanes_processed=1000, lanes_useful=10),
        _record([("mine", 50.001, 89.999 + end_shift_ms, -1),
                 ("level", 52, 88, 0), ("plan_build", 52, 55, 1),
                 ("block", 55, 85, 1)],
                lanes_processed=3000, lanes_useful=20),
    ]


def _run(records, tr=TRACE):
    queries = [SimpleNamespace(result=SimpleNamespace(trace=r)) for r in records]
    return SimpleNamespace(queries=queries, trace=tr)


def test_records_are_placed_on_the_traces_clock():
    placed = program_spans.aligned(_run(_records()))
    assert [len(q) for q in placed] == [7, 4]
    name, start, end, parent = placed[0][0]
    assert (name, parent) == ("mine", -1)
    assert start == pytest.approx(10.001 * MS) and end == pytest.approx(39.999 * MS)
    assert placed[1][3][:3] == ("block", pytest.approx(55 * MS),
                                pytest.approx(85 * MS))


def test_idle_inside_blocks_per_block():
    # [14, 26]: 1 + 1 ms; [26, 36]: 4 + 1 ms; [55, 85]: 5 + 5 ms
    assert program_spans.block_idle_ms(_run(_records())) \
        == pytest.approx(17 / 3)


def test_idle_inside_mine_under_no_other_span():
    # query 1: [10.001, 12] and [38, 39.999]; query 2: [50.001, 52] and
    # [88, 89.999]; each piece idle throughout, over 65 ms of idle
    assert program_spans.idle_unspanned(_run(_records())) \
        == pytest.approx(100 * 4 * 1.999 / 65)


def test_clocks_that_disagree_give_no_number():
    run = _run(_records(end_shift_ms=2.5))
    assert program_spans.aligned(run) is None
    assert program_spans.block_idle_ms(run) is None
    assert program_spans.idle_unspanned(run) is None
    # within 1 ms the pairing holds
    assert program_spans.aligned(_run(_records(end_shift_ms=0.5))) is not None
    # one query more in the trace than in the window's records
    extra = dict(TRACE, spans=TRACE["spans"] + [["bench.query", 95 * MS, MS]])
    assert program_spans.aligned(_run(_records(), extra)) is None


def test_a_program_without_a_record_gives_no_number():
    bare = SimpleNamespace(queries=[SimpleNamespace(result=SimpleNamespace())],
                           trace=TRACE, spans=None)
    for name in ("plan_build_ms.query", "block_idle_ms.query",
                 "idle_unspanned.search", "compiles.query",
                 "lane_yield.search"):
        assert bench_run.reader(name)(bare) is None
    assert program_spans.aligned(_run(_records(), None)) is None


def test_counter_and_span_readers():
    run = _run(_records())
    read = bench_run.reader
    assert read("compiles.query")(run) == 1.0
    assert read("lane_yield.search")(run) == pytest.approx(100 * 30 / 4000)
    assert read("plan_build_ms.query")(run) == pytest.approx((1 + 3) / 2)
    assert read("block_idle_ms.search")(run) == read("block_idle_ms.query")(run)
    assert read("idle_unspanned.query")(run) \
        == read("idle_unspanned.search")(run)


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")))
def test_a_root_alone_leaves_all_of_its_query_idle_unnamed(path):
    """On a recorded chip trace, a record of nothing but its ``mine`` span
    leaves unnamed the idle time the benchmark puts under its own spans
    inside ``bench.query``."""
    tr = json.loads(path.read_text())
    queries = sorted((s, s + d) for n, s, d in tr["spans"] if n == "bench.query")
    records = [QueryTrace(spans=[Span("mine", ORIGIN + s + 1000,
                                      ORIGIN + e - 1000, -1, {})])
               for s, e in queries]
    gaps = dict(trace.idle_gaps(tr, n=1000))
    inside = sum(v for k, v in gaps.items()
                 if k in ("bench.query", "bench.cand_build", "bench.plan",
                          "bench.generation"))
    total = sum(gaps.values())
    got = program_spans.idle_unspanned(_run(records, tr))
    assert got == pytest.approx(100 * inside / total, rel=1e-3)
