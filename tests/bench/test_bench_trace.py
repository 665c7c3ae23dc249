"""The reduction from a device trace to busy time, idle share, step time
by program name, the heaviest operations and idle gaps by host span."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"

# One device, a 100 ns window.  Operations overlap, nest (a loop and the
# two fusions of its body) and cross the window's edges.
HAND = {
    "devices": {"0": {
        "ops": [["pre", -5, 7], ["while.1", 10, 20], ["fusion.2", 12, 8],
                ["fusion.3", 20, 8], ["copy.4", 30, 10], ["fusion.5", 50, 9],
                ["tail", 95, 15]],
        "modules": [["jit_step(1)", 10, 20], ["jit_step(2)", 50, 10],
                    ["jit_other", 95, 10]]}},
    "spans": [["bench.window", 0, 100], ["bench.query", 0, 70],
              ["bench.cand_build", 2, 8], ["bench.generation", 60, 10]],
}


def test_busy_union_and_idle_share():
    # union [0,2] [10,40] [50,59] [95,100]
    assert trace.window(HAND) == (0, 100)
    assert trace.busy_ns(HAND) == 46
    assert trace.idle_share(HAND) == pytest.approx(0.54)


def test_step_time_by_program_name():
    assert trace.module_time(HAND, "jit_step") == (30, 2)
    assert trace.module_time(HAND, "jit_missing") == (0, 0)


def test_heaviest_ops_net_of_their_body():
    ops = dict(trace.top_ops(HAND, n=20))
    assert ops["while.1"] == pytest.approx(4e-9)
    assert ops["fusion.2"] == pytest.approx(8e-9)
    assert "pre" not in ops and "tail" not in ops   # not inside the window
    assert trace.top_ops(HAND, n=1)[0][0] == "copy.4"


def test_idle_gaps_by_host_span():
    gaps = dict(trace.idle_gaps(HAND))
    assert gaps == pytest.approx({"bench.cand_build": 8e-9,
                                  "bench.query": 11e-9,
                                  "bench.generation": 10e-9,
                                  "bench.window": 25e-9})
    assert sum(gaps.values()) == pytest.approx(1e-9 * (100 - 46))


def test_no_window_no_numbers():
    empty = {"devices": HAND["devices"], "spans": []}
    assert trace.idle_share(empty) is None
    assert trace.busy_ns(empty) is None
    assert trace.top_ops(empty) == [] and trace.idle_gaps(empty) == []


def _brute(tr, step):
    """Busy ns and idle ns by innermost span, on a grid of ``step`` ns."""
    import numpy as np

    lo, hi = trace.window(tr)
    t = lo + step / 2 + step * np.arange(int((hi - lo) // step))
    busy = np.zeros(t.size, bool)
    for _, s, d in tr["devices"]["0"]["ops"]:
        busy[(t >= s) & (t < s + d)] = True
    name = np.full(t.size, "", object)
    width = np.full(t.size, np.inf)
    for n, s, d in tr["spans"]:
        inside = (t >= s) & (t < s + d) & (d < width)
        name[inside], width[inside] = n, d
    idle = {}
    for n in set(name[~busy]):
        idle[n] = float((name[~busy] == n).sum() * step)
    return float(busy.sum() * step), idle


def test_idle_gaps_split_at_span_edges():
    busy, idle = _brute(HAND, 0.5)
    assert trace.busy_ns(HAND) == busy
    assert dict(trace.idle_gaps(HAND)) == pytest.approx(
        {k: v / 1e9 for k, v in idle.items()})


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")))
def test_a_recorded_chip_trace(path):
    tr = json.loads(path.read_text())
    lo, hi = trace.window(tr)
    busy = trace.busy_ns(tr)
    assert 0 < busy <= hi - lo
    gaps = dict(trace.idle_gaps(tr, n=1000))
    assert sum(gaps.values()) == pytest.approx((hi - lo - busy) / 1e9, rel=1e-9)
    want_busy, want_idle = _brute(tr, 2000.0)
    assert busy == pytest.approx(want_busy, rel=0.02)
    assert gaps == pytest.approx({k: v / 1e9 for k, v in want_idle.items()},
                                 rel=0.02)
    # the window opens with the host's candidate build: the device waits
    assert gaps["bench.cand_build"] > 0
    ns, count = trace.module_time(tr, "jit_convert_element_type")
    assert count > 0 and 0 < ns <= busy
    assert trace.top_ops(tr, n=3)[0][1] > 0
