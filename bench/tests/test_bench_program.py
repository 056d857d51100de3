"""The reductions of the program's own spans and counters
(`bench.harness.program`) on small synthetic inputs, and on a reduced
traced run with the program's recorder on while the profiler runs."""

import argparse
import math

import pytest

from bench.harness import program as P


def test_idle_goes_to_the_innermost_program_span():
    # device busy 0-10, 30-40, 70-80 of a 0-100 window; host: a step
    # with a drain inside and an engine phase inside that, a sample
    # with a sync inside, then nothing
    ops = [("a", 0, 10), ("b", 30, 40), ("c", 70, 80)]
    spans = [("frontdoor.step", 5, 60), ("frontdoor.drain", 8, 30),
             ("engine.execute", 12, 20), ("model.sample", 40, 55),
             ("model.sync", 45, 50)]
    idle = dict(P.idle_by_span(ops, spans, 0, 100))
    # idle 10-30: drain 10-12, execute 12-20, drain 20-30; idle 40-70:
    # sample 40-45, sync 45-50, sample 50-55, step 55-60, none 60-70;
    # idle 80-100: none
    assert idle == pytest.approx({
        "frontdoor.drain": 12e-9, "engine.execute": 8e-9,
        "model.sample": 10e-9, "model.sync": 5e-9,
        "frontdoor.step": 5e-9, "none": 30e-9})
    assert sum(idle.values()) == pytest.approx(70e-9)
    assert P.plane_idle_share(list(idle.items()), 0, 100) == \
        pytest.approx(20.0)


def test_outside_any_span_is_none_and_ties_go_to_the_shorter():
    assert P.idle_by_span([("a", 0, 10)], [], 0, 20) == [("none", 1e-8)]
    spans = [("frontdoor.step", 0, 20), ("frontdoor.plan", 0, 5)]
    assert P.innermost(spans) == [("frontdoor.plan", 0, 5),
                                  ("frontdoor.step", 5, 20)]
    assert dict(P.idle_by_span([], spans, 0, 30)) == pytest.approx(
        {"frontdoor.plan": 5e-9, "frontdoor.step": 15e-9, "none": 10e-9})


def test_recorder_reductions():
    ms = 1_000_000
    events = [("request.queued", 0, 4 * ms, None, 1),
              ("frontdoor.step", 4 * ms, 10 * ms, None, None),
              ("frontdoor.plan", 4 * ms, 5 * ms, 1, None),
              ("request.staged", 4 * ms, 9 * ms, None, 1),
              ("frontdoor.stage", 5 * ms, 6 * ms, 1, 1),
              ("frontdoor.drain", 6 * ms, 8 * ms, 1, None),
              ("engine.execute", 6 * ms, 7 * ms, 5, None),
              ("model.sample", 9 * ms, 10 * ms, 1, 1),
              ("model.sync", 9 * ms, 9 * ms + 1, 7, None),
              ("model.sync", 10 * ms - 1, 10 * ms, 7, None),
              ("frontdoor.step", 10 * ms, 12 * ms, None, None),
              ("frontdoor.build", 10 * ms, 11 * ms, 10, 1),
              ("model.sample", 11 * ms, 12 * ms, 10, 1),
              ("model.sync", 11 * ms, 11 * ms + 1, 12, None),
              ("model.sync", 11 * ms + 1, 11 * ms + 2, 12, None)]
    counters = {"plane_bytes": 3_000_000}
    assert P.plane_ms_per_step(events) == pytest.approx(2.0)
    assert P.plane_bytes_per_step(events, counters) == pytest.approx(1.5)
    assert P.host_syncs_per_token(events) == 2.0
    assert P.queue_wait_ms_mean(events) == pytest.approx(4.0)
    assert P.ttft_split_ms(events) == pytest.approx(
        {"queued": 4.0, "staged": 5.0, "first_sample": 1.0})
    table = P.span_table(events)
    assert table["frontdoor.step"] == pytest.approx((2, 8e-3, 1e-3))
    assert table["frontdoor.drain"] == pytest.approx((1, 2e-3, 1e-3))
    assert P.plane_ms_per_step([]) is None
    assert P.host_syncs_per_token(events[:7]) is None
    assert P.queue_wait_ms_mean(events[1:]) is None


def test_reduced_traced_run_with_the_recorder_on(monkeypatch):
    """The reduced docqa cell through the harness, with the program's
    recorder on exactly while the profiler runs: every program metric
    reads a finite value, and two device reads go to each token."""
    from bench import run as bench_run
    from bench.harness import spec, trace as trace_mod
    from repro import obs

    rec = obs.DEFAULT
    spans = []

    class Recording(bench_run.Tracer):
        def open(self):
            super().open()
            rec.enabled = True

        def close(self):
            rec.enabled = False
            super().close()

    def load(log_dir):
        spans.extend(P.load_spans(log_dir))
        return load_trace(log_dir)

    load_trace = trace_mod.load
    monkeypatch.setattr(bench_run, "Tracer", Recording)
    monkeypatch.setattr(trace_mod, "load", load)
    runs = []
    args = argparse.Namespace(workload="chatglm3-6b.docqa",
                              seed=2 ** 33 + 5, seconds=3.0, trace=1,
                              reduced=True)
    info = {"platform": "cpu", "kind": "cpu", "count": 1}
    peaks = {"bf16_flops_per_s": math.nan, "hbm_bytes_per_s": math.nan}
    try:
        out = bench_run.execute(args, spec.cell(args.workload), info,
                                peaks, inspect=runs.append)
        events, counters = list(rec.events), dict(rec.counters)
    finally:
        rec.enabled = False
        rec.clear()
    assert out["correct"] is True
    t0, t1 = runs[0].trace_window
    idle = P.idle_by_span(runs[0].trace.ops, spans, t0, t1)
    assert sum(s for _, s in idle) == pytest.approx(
        (t1 - t0 - trace_mod.busy_ns(runs[0].trace.ops, t0, t1)) / 1e9)
    assert {"frontdoor.step", "frontdoor.drain"} <= {n for n, _ in idle}
    values = [P.plane_ms_per_step(events),
              P.plane_bytes_per_step(events, counters),
              P.plane_idle_share(idle, t0, t1),
              P.host_syncs_per_token(events),
              P.queue_wait_ms_mean(events)]
    values += list(P.ttft_split_ms(events).values())
    assert all(v is not None and math.isfinite(v) for v in values), values
    assert P.host_syncs_per_token(events) == 2.0
