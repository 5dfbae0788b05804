"""The reduction of the program's spans and operator scopes, on
hand-written traces, and the device-plane decoding on a hand-encoded
XSpace."""
import numpy as np
import pytest

from benchmarks.flare_bench import program_trace as PT

DEV = "/device:TPU:0"


def _trace(spans=(), host=(), ops=(), modules=()):
    return {"spans": [list(s) for s in spans],
            "host": [["bench:traced", 0.0, 10.0]] + [list(h) for h in host],
            "device_ops": [list(o) for o in ops],
            "modules": [list(m) for m in modules]}


def test_span_p95_per_name_inside_the_window():
    waits = [0.001 * k for k in range(1, 21)]
    spans = [["serve.wait", 1.0 + k, 1.0 + k + w, {"req": k}]
             for k, w in enumerate(waits[:8])]
    spans += [["serve.wait", 2.0, 2.0 + w, {}] for w in waits[8:]]
    # another span's name, and a wait that starts before the window
    spans += [["serve.dispatch", 3.0, 3.5, {}],
              ["serve.wait", -0.5, 0.5, {}]]
    tr = _trace(spans=spans)
    assert PT.span_p95_ms(tr, "serve.wait") == pytest.approx(
        np.percentile(waits, 95) * 1e3)
    assert PT.span_p95_ms(tr, "serve.dispatch") == pytest.approx(500.0)
    assert PT.durations(tr, "serve.wait") == pytest.approx(
        waits[:8] + waits[8:])


def test_percentile_matches_numpy():
    rng = np.random.default_rng(3)
    for n in (1, 2, 7, 160):
        xs = list(rng.exponential(size=n))
        for q in (50, 95, 99):
            assert PT.percentile(xs, q) == pytest.approx(
                np.percentile(xs, q))
    assert PT.percentile([], 95) is None


def test_no_span_no_window_or_no_trace_reads_none():
    tr = _trace(spans=[["serve.wait", 1.0, 1.1, {}]])
    assert PT.span_p95_ms(tr, "serve.finalize") is None
    assert PT.span_p95_ms(None, "serve.wait") is None
    no_window = dict(tr, host=[])
    assert PT.span_p95_ms(no_window, "serve.wait") is None


def test_scope_seconds_per_stream():
    # one stream: q14 at [1, 4], q6 at [4.5, 5], q3 at [6, 9]; the device
    # clock reads 2 ms ahead of the host's
    d = 2e-3
    host = [["bench:q14", 1.0, 4.0], ["bench:q6", 4.5, 5.0],
            ["bench:q3", 6.0, 9.0]]
    modules = [[DEV, "jit_fn(1)", 1.1 + d, 3.9 + d],
               [DEV, "jit_fn(2)", 4.6 + d, 4.9 + d],
               [DEV, "jit_fn(3)", 6.1 + d, 8.9 + d]]
    ops = [
        # q14: a while loop [1.2, 2.2] and two body ops inside it, then
        # a gather [3.0, 3.5]
        [DEV, "%while.4", 1.2 + d, 2.2 + d, None],
        [DEV, "%fusion.8", 1.2 + d, 1.7 + d, "flare:join.probe"],
        [DEV, "%fusion.9", 1.6 + d, 2.2 + d, "flare:join.probe"],
        [DEV, "%fusion.2", 3.0 + d, 3.5 + d, "flare:join.gather"],
        # q6 is not a join query: its probe-scoped op does not count
        [DEV, "%fusion.1", 4.6 + d, 4.8 + d, "flare:join.probe"],
        # q3: a probe op, and an aggregate
        [DEV, "%fusion.3", 6.2 + d, 6.7 + d, "flare:join.probe"],
        [DEV, "%fusion.4", 7.0 + d, 8.0 + d, "flare:agg"],
    ]
    tr = _trace(host=host, ops=ops, modules=modules)
    joins = ["q3", "q14"]
    assert PT.scope_seconds(tr, "flare:join.probe", joins) == \
        pytest.approx(1.0 + 0.5)
    assert PT.scope_seconds(tr, "flare:join.gather", joins) == \
        pytest.approx(0.5)
    assert PT.scope_seconds(tr, "flare:sort", joins) is None
    run = {"trace": {"calls": {"q14": 2, "q6": 2, "q3": 2}}}
    assert PT.stream_scope_s(run, tr, "flare:join.probe", joins, "q14") \
        == pytest.approx(0.75)
    assert PT.stream_scope_s({"trace": None}, tr, "flare:join.probe",
                             joins, "q14") is None
    assert PT.stream_scope_s(run, None, "flare:join.probe", joins,
                             "q14") is None


def test_scope_seconds_clipped_to_the_window():
    # inside its call by its middle (9.9), clipped at the window's end
    tr = _trace(host=[["bench:q3", 9.0, 9.9]],
                ops=[[DEV, "%fusion.1", 9.6, 10.2, "flare:join.probe"]],
                modules=[[DEV, "jit_fn(1)", 9.1, 9.85]])
    assert PT.scope_seconds(tr, "flare:join.probe", ["q3"]) == \
        pytest.approx(0.4)
    # a call that runs past the window is not in it
    tr = _trace(host=[["bench:q3", 9.0, 11.0]],
                ops=[[DEV, "%fusion.1", 9.5, 9.7, "flare:join.probe"]],
                modules=[[DEV, "jit_fn(1)", 9.1, 9.85]])
    assert PT.scope_seconds(tr, "flare:join.probe", ["q3"]) is None
    # an op whose middle lies outside every call is not counted
    tr = _trace(host=[["bench:q3", 9.0, 9.5]],
                ops=[[DEV, "%fusion.1", 9.4, 9.8, "flare:join.probe"]],
                modules=[[DEV, "jit_fn(1)", 9.1, 9.45]])
    assert PT.scope_seconds(tr, "flare:join.probe", ["q3"]) is None


def test_scope_of_a_tf_op_path():
    assert PT.scope("jit(q3)/flare:join.probe/jit(searchsorted)/while:") \
        == "flare:join.probe"
    assert PT.scope("jit(fn)/vmap()/flare:agg/reduce_sum:") == "flare:agg"
    assert PT.scope("jit(fn)/flare:filter_scalar_agg/flare:agg/x") \
        == "flare:agg"
    assert PT.scope("jit(fn)/reduce_sum") is None
    assert PT.scope(None) is None


# ---------------------------------------------------------------------------
# a hand-encoded XSpace
# ---------------------------------------------------------------------------


def _varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _int(field, x):
    return _varint(field << 3) + _varint(x)


def _bytes(field, b):
    if isinstance(b, str):
        b = b.encode()
    return _varint(field << 3 | 2) + _varint(len(b)) + b


def _map_entry(field, key, value):
    return _bytes(field, _int(1, key) + _bytes(2, value))


def test_device_plane_decoding():
    stat_meta = (_map_entry(5, 1, _int(1, 1) + _bytes(2, "tf_op"))
                 + _map_entry(5, 2, _int(1, 2) + _bytes(2, "flops"))
                 + _map_entry(5, 3, _int(1, 3) + _bytes(
                     2, "jit(q3)/flare:join.gather/gather:")))
    event_meta = (
        _map_entry(4, 10, _int(1, 10) + _bytes(2, "%fusion.8 = s32[8] f()")
                   + _bytes(5, _int(1, 2) + _int(4, 99))
                   + _bytes(5, _int(1, 1) + _bytes(
                       5, "jit(q3)/flare:join.probe/jit(searchsorted)/"
                          "while/body/gather:")))
        # the scope given by reference to a stat metadata's name
        + _map_entry(4, 11, _int(1, 11) + _bytes(2, "%fusion.2 = f32[8] g()")
                     + _bytes(5, _int(1, 1) + _int(7, 3)))
        + _map_entry(4, 12, _int(1, 12) + _bytes(2, "%copy.1 = f32[8] c()"))
        + _map_entry(4, 13, _int(1, 13) + _bytes(2, "jit_fn(42)")))
    ops_line = (_bytes(2, "XLA Ops") + _int(3, 1000)
                + _bytes(4, _int(1, 10) + _int(2, 2_000_000)
                         + _int(3, 500_000))
                + _bytes(4, _int(1, 11) + _int(2, 3_000_000)
                         + _int(3, 250_000))
                + _bytes(4, _int(1, 12) + _int(2, 4_000_000)
                         + _int(3, 1_000)))
    mod_line = (_bytes(2, "XLA Modules")
                + _bytes(4, _int(1, 13) + _int(2, 1_000_000)
                         + _int(3, 5_000_000)))
    device = (_int(1, 7) + _bytes(2, DEV) + _bytes(3, ops_line)
              + _bytes(3, mod_line) + event_meta + stat_meta)
    host = _int(1, 8) + _bytes(2, "/host:CPU")
    space = _bytes(1, host) + _bytes(1, device) + _bytes(4, "hostname")

    ops, modules = PT._device_planes(space)
    assert [o[1] for o in ops] == ["%fusion.8", "%fusion.2", "%copy.1"]
    assert [o[4] for o in ops] == ["flare:join.probe", "flare:join.gather",
                                   None]
    # line timestamp 1000 ns plus the event's offset, in seconds
    assert ops[0][2] == pytest.approx(1e-6 + 2e-6)
    assert ops[0][3] - ops[0][2] == pytest.approx(0.5e-6)
    assert modules == [[DEV, "jit_fn(42)", pytest.approx(1e-6),
                        pytest.approx(6e-6)]]
