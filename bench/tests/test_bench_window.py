"""A traced run records the window's last ``trace.seconds`` and stops the
profiler after the window has closed, so that it serves as an untraced
run does: a whole run of a tiny configuration on the CPU (the harness's
look for a chip skipped), with a stub profiler whose stop takes seconds,
as the real one's does at the cells' size."""
import time

import jax
import pytest

from bench import harness, spec, tracing
from bench.tests.test_bench_correct import CELL, LIMITS, MIX, tiny

STOP_S = 3.0
SEED = 2**31 + 23


class StubProfiler:
    """Records when the harness starts and stops it; stopping sleeps."""

    def __init__(self):
        self.calls = []

    def start_trace(self, *a, **kw):
        self.calls.append(("start", time.perf_counter()))

    def stop_trace(self):
        self.calls.append(("stop", time.perf_counter()))
        time.sleep(STOP_S)


@pytest.fixture
def stub(monkeypatch):
    prof = StubProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", prof.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", prof.stop_trace)
    # the stub writes no trace: its reduction finds no window
    monkeypatch.setattr(tracing, "load",
                        lambda d: {"host": [], "modules": [], "ops": []})
    return prof


def run(trace, seconds, trace_s, tmp_path):
    mix = dict(MIX, trace={"seconds": trace_s})
    return harness.run_cell(spec.load_benchmark(), CELL, SEED, seconds,
                            trace, t_start=time.perf_counter(),
                            trace_dir=str(tmp_path), cfg=tiny(), mix=mix,
                            limits=LIMITS)


def test_traced_run_serves_as_an_untraced_one(stub, tmp_path):
    seconds, trace_s = 4.0, 1.5
    plain = run(False, seconds, trace_s, tmp_path)
    traced = run(True, seconds, trace_s, tmp_path)
    assert [c for c, _ in stub.calls] == ["start", "stop"]
    t0, t1 = traced["window"]["traced_s"]
    # the stretch is the window's tail, and the stop comes after the close
    assert seconds - trace_s <= t0 < seconds - trace_s + 0.5
    assert t1 >= seconds
    assert stub.calls[1][1] - stub.calls[0][1] == pytest.approx(t1 - t0,
                                                                abs=0.05)
    # the stop's seconds cost the window nothing: both runs finish about
    # as many requests (a stop inside the window would cost it up to
    # three of its four seconds; two runs on the CPU differ by a tenth)
    assert plain["correct"] and traced["correct"]
    n_plain, n_traced = (r["window"]["finished"] for r in (plain, traced))
    assert n_plain >= 4
    assert n_traced >= 0.75 * n_plain
    assert "traced_s" not in plain["window"]


def test_window_shorter_than_the_traced_stretch(stub, tmp_path):
    seconds = 2.0
    res = run(True, seconds, 6.0, tmp_path)
    t0, t1 = res["window"]["traced_s"]
    assert 0.0 <= t0 < 0.5 and t1 >= seconds
    assert [c for c, _ in stub.calls] == ["start", "stop"]
    assert res["correct"]
