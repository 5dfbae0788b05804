"""The trace reduction: idle share and per-query device time, on a
hand-made trace and on a small trace recorded on a TPU v5e."""
import json
import os

import numpy as np
import pytest

from benchmarks.flare_bench import traces

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "scan_trace_small.json")
DEV = "/device:TPU:0"


def test_hand_made_trace():
    # window [0, 10]; ops [1,3] and [2,4] overlap (busy 3), [6,7] in q6,
    # [9,12] runs past the window (busy 1 inside it).  The q1 program
    # [0.2, 4] starts before its call (device clock ahead): its middle
    # is inside q1's call.
    trace = {
        "host": [["bench:traced", 0.0, 10.0], ["bench:q1", 0.5, 5.0],
                 ["bench:q6", 5.5, 8.0]],
        "device_ops": [[DEV, "a", 1.0, 3.0], [DEV, "b", 2.0, 4.0],
                       [DEV, "c", 6.0, 7.0], [DEV, "d", 9.0, 12.0]],
        "modules": [[DEV, "jit_fn(1)", 0.2, 4.0],
                    [DEV, "jit_fn(2)", 6.0, 7.0]],
    }
    r = traces.reduce(trace)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(5.0)
    assert r["device_s"] == {"q1": pytest.approx(3.8),
                             "q6": pytest.approx(1.0)}
    assert r["calls"] == {"q1": 1, "q6": 1}
    assert r["programs"] == {"jit_fn": [2, pytest.approx(4.8)]}
    gaps = dict((round(t, 6), n) for n, t in r["breakdown"]["idle_gaps"])
    assert gaps[2.0] == "q6"          # [4, 6]: q1 ends at 5, q6 opens at 5.5
    assert gaps[1.0] in ("q1", "q6")  # [0, 1] and [8, 9]
    assert r["breakdown"]["device_ops"][0][0] in ("a", "b")


def test_short_calls_are_found_across_a_clock_offset():
    """Programs much shorter than the device clock's lead over the
    host's still count for the short calls that ran them."""
    host = [["bench:traced", 0.0, 1.0]]
    modules = []
    t = 0.01
    for k in range(20):
        for q, call, run in (("long", 0.020, 0.015), ("q6", 0.002, 0.0002)):
            host.append([f"bench:{q}", t, t + call])
            # the program ends just before its call reads the rows, on a
            # device clock 3 ms ahead
            modules.append([DEV, f"jit_fn({q})", t + call - run - 1e-4
                            + 3e-3, t + call - 1e-4 + 3e-3])
            t += call + 1e-5
    r = traces.reduce({"host": host, "device_ops": modules,
                       "modules": modules})
    assert r["clock_offset_s"] == pytest.approx(3e-3, abs=2e-4)
    assert r["calls"] == {"long": 20, "q6": 20}
    assert r["device_s"]["q6"] == pytest.approx(20 * 2e-4)


def test_no_window_or_no_device_reads_nothing():
    assert traces.reduce({"host": [], "device_ops": [], "modules": []}) is None
    assert traces.reduce({"host": [["bench:traced", 0.0, 1.0]],
                          "device_ops": [], "modules": []}) is None


def _grid_busy(ops, lo, hi, step=1e-6):
    """Busy seconds on a 1 us grid: an independent count."""
    n = int(round((hi - lo) / step))
    busy = np.zeros(n, bool)
    for _, _, s, e in ops:
        a = max(0, int(np.floor((s - lo) / step)))
        b = min(n, int(np.ceil((e - lo) / step)))
        if b > a:
            busy[a:b] = True
    return busy.sum() * step


def test_recorded_trace_matches_an_independent_count():
    """Three q1 and three q6 calls of the SF 10 scan stream, recorded on
    one TPU v5e and cut short (``tests/data``)."""
    with open(RECORDED) as f:
        trace = json.load(f)
    r = traces.reduce(trace)
    lo, hi = [(s, e) for n, s, e in trace["host"] if n == "bench:traced"][0]
    # the device clock read about 1 ms behind the host's here (q6's
    # program starts 1.0 ms before its call on the device's clock)
    off = r["clock_offset_s"]
    assert -2e-3 < off <= -1e-3
    shifted = [[d, n, s - off, e - off] for d, n, s, e in trace["device_ops"]]
    assert r["busy_s"] == pytest.approx(
        _grid_busy(shifted, lo, hi), abs=2e-6 * len(
            trace["device_ops"]))
    assert 0.0 < r["busy_s"] < r["window_s"]
    # by hand: the q1 program is jit_fn(7891322843882367421) (two runs
    # in the cut), the q6 program jit_fn(10228287564091293591) (three)
    for q, module, runs in (("q1", "7891322843882367421", 2),
                            ("q6", "10228287564091293591", 3)):
        durations = [e - s for _, name, s, e in trace["modules"]
                     if module in name]
        assert len(durations) == runs == r["calls"][q]
        assert r["device_s"][q] == pytest.approx(sum(durations))
    assert r["programs"]["jit_fn"][0] == 5
