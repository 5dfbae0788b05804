"""CPU rehearsals of every cell at SF 0.01, calling the harness directly
(the command itself refuses the CPU), and the check's control and
faults: the bfloat16 reference in the program's place, and the timed
path broken underneath, must each come out as not correct."""
import os
import subprocess
import sys
import time

import pytest

from benchmarks.flare_bench import control, harness

CELLS = [w["name"] for w in harness.spec()["workloads"]]
SEED = 2**31 + 11  # more than 32 signed bits hold


def _cpu(chips):
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": "TPU v5 lite",
            "count": len(devs)}


def _small(workload):
    _, _, config, mix = harness.cell_files(workload)
    config["scale_factor"] = 0.01
    if "rate_per_s" in mix:
        mix["rate_per_s"] = 50.0
    return config, mix


@pytest.fixture(autouse=True)
def _no_jax_cache(monkeypatch):
    # CPU programs stay out of the checkout's JAX cache
    from repro.persist import xla_cache
    monkeypatch.setattr(xla_cache, "enable_jax_compile_cache", lambda: "")


def _run(workload, trace=False, seconds=1.5, flush_interval_s=None):
    config, mix = _small(workload)
    if flush_interval_s is not None and "flush_interval_s" in mix:
        mix["flush_interval_s"] = flush_interval_s
    return harness.run_cell(workload, SEED, seconds, trace,
                            time.perf_counter(), config=config, mix=mix,
                            require_chip=_cpu)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_on_cpu(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = harness.spec()["end_to_end"]
    want = {m["name"] for m in e2e
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/flare_bench/run.py",
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_check(workload):
    config, mix = _small(workload)
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            workload + ".json"))
    got = control.readings(workload, SEED, config, mix)
    assert (got["rel_gap"] > limits["rel_gap"]
            or got["mismatched"] > limits["mismatched"]), got


def _alter_answers(monkeypatch):
    """Every answer's first float column 0.1% off, where it is made."""
    import numpy as np
    from repro.core import lower as L
    compact = L.Result.compact

    def altered(self):
        out = dict(compact(self))
        for k, v in out.items():
            v = np.asarray(v)
            if v.dtype.kind == "f":
                out[k] = v * 1.001
                break
        return out
    monkeypatch.setattr(L.Result, "compact", altered)


def _drop_half_batch(monkeypatch):
    """A coalesced batch computes its first half of the bindings only and
    hands those answers to the second half too."""
    from repro.core import stages as S
    batch = S.Compiled.batch

    def half(self, bindings, block=True):
        bindings = list(bindings)
        k = max(1, len(bindings) // 2)
        got = batch(self, bindings[:k], block=block)
        return got + [got[i % k] for i in range(len(bindings) - k)]
    monkeypatch.setattr(S.Compiled, "batch", half)


def _drop_tile(monkeypatch):
    """The native grouped scan loses one [8, 128] tile of rows: their
    codes match no group, so every sum and count of q1 misses them."""
    from repro.kernels.segmented_reduce import kernel as SR_K
    multi = SR_K.segmented_multi_sum

    def dropped(value_fn, cols, codes, *args, **kw):
        return multi(value_fn, cols, codes.at[:8].set(-1), *args, **kw)
    monkeypatch.setattr(SR_K, "segmented_multi_sum", dropped)


def _mix(workload):
    return harness.cell_files(workload)[3]


FAULTS = [(w, "altered_answer") for w in CELLS] + [
    (w, "half_batch") for w in CELLS if _mix(w)["loop"] != "stream"] + [
    (w, "dropped_tile") for w in CELLS if "q1" in _mix(w).get("queries", ())]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    {"altered_answer": _alter_answers,
     "half_batch": _drop_half_batch,
     "dropped_tile": _drop_tile}[fault](monkeypatch)
    # a slower flush worker gathers several requests into each batch
    out = _run(workload, flush_interval_s=0.02)
    assert not out["correct"], out["checks"]
    if fault == "dropped_tile":
        # the counts catch it on their own, whatever the floats' limit
        assert out["checks"]["mismatched"]["value"] > 0, out["checks"]
