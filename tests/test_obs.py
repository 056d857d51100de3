"""`repro.obs`: the recorder off costs nothing and records nothing; on,
it nests spans, keeps request ids and sums counters; the front door, its
engine and `StepLM` record the serving path's span tree without changing
what they serve."""

import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.serve.kvcache import KVLayout
from repro.serve.sched import HashLM, ServeFrontDoor, ServeRequest

SMALL = KVLayout(n_pages=10, page_size=4, n_kv_heads=2, head_dim=4,
                 itemsize=4)


def _requests(n, seed):
    rng = np.random.default_rng(seed)
    return [ServeRequest(
        rid=rid, prompt=list(map(int, rng.integers(0, 64,
                                                   rng.integers(2, 13)))),
        max_new_tokens=int(rng.integers(2, 11)),
        temperature=float(rng.choice([0.0, 0.8])),
        seed=int(rng.integers(0, 1 << 31))) for rid in range(n)]


def _serve(reqs, model=None, layout=SMALL, **kw):
    fd = ServeFrontDoor(model or HashLM(layout.row_bytes), layout,
                        max_seq_len=24, **kw)
    for r in reqs:
        fd.submit(r)
    fd.run()
    return fd


@pytest.fixture
def default_recorder():
    """The module's recorder, which every layer reads: off and empty
    before and after the test."""
    rec = obs.DEFAULT
    assert not rec.enabled
    rec.clear()
    yield rec
    rec.enabled = False
    rec.clear()


def test_off_reads_no_clock_and_allocates_nothing(monkeypatch):
    rec = obs.Recorder()

    def no_clock():
        raise AssertionError("clock read while off")

    monkeypatch.setattr(obs.time, "perf_counter_ns", no_clock)
    assert rec.span("a") is rec.span("b", rid=3)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(1000):
            with rec.span("a", rid=i):
                rec.count("n")
                rec.begin("q", i)
                rec.end("q", i)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    only = [tracemalloc.Filter(True, obs.__file__)]
    grown = after.filter_traces(only).compare_to(
        before.filter_traces(only), "filename")
    assert sum(s.size_diff for s in grown) == 0
    assert rec.events == [] and rec.counters == {}


def test_on_nests_parents_keeps_rids_and_sums_counters():
    rec = obs.Recorder(enabled=True)
    with rec.span("step"):
        with rec.span("sample", rid=7):
            with rec.span("sync"):
                rec.count("syncs")
        with rec.span("drain"):
            rec.count("syncs", 4)
    rec.begin("queued", 7)
    rec.end("queued", 7)
    rec.end("queued", 8)                # never begun: nothing
    with pytest.raises(ValueError):
        with rec.span("fails"):
            raise ValueError
    names = [e[0] for e in rec.events]
    assert names == ["step", "sample", "sync", "drain", "queued", "fails"]
    assert [e[3] for e in rec.events] == [None, 0, 1, 0, None, None]
    assert [e[4] for e in rec.events] == [None, 7, None, None, 7, None]
    for _, s, e, parent, _ in rec.events:
        assert s <= e
        if parent is not None:
            assert rec.events[parent][1] <= s and e <= rec.events[parent][2]
    assert rec.counters == {"syncs": 5}
    rec.clear()
    assert rec.events == [] and rec.counters == {}


def test_front_door_serves_the_same_and_records_the_span_tree(
        default_recorder):
    rec = default_recorder
    off = _requests(14, seed=2)
    on = _requests(14, seed=2)
    fd_off = _serve(off, max_running=6, low_watermark=1)
    assert rec.events == [] and rec.counters == {}
    rec.enabled = True
    fd_on = _serve(on, max_running=6, low_watermark=1)
    rec.enabled = False
    assert fd_off.alloc.stats.preemptions > 0     # swaps happened
    assert [r.output for r in on] == [r.output for r in off]
    assert fd_on.metrics.cycles == fd_off.metrics.cycles
    assert fd_on.alloc.stats.preemptions == fd_off.alloc.stats.preemptions
    assert not hasattr(fd_on.metrics, "per_step")

    ev = rec.events
    names = {e[0] for e in ev}
    assert names == {"frontdoor.step", "frontdoor.plan", "frontdoor.stage",
                     "frontdoor.build", "frontdoor.drain", "engine.lower",
                     "engine.simulate", "engine.execute", "engine.deliver",
                     "request.queued", "request.staged"}
    parent = {"frontdoor.step": None, "frontdoor.plan": "frontdoor.step",
              "frontdoor.stage": "frontdoor.step",
              "frontdoor.build": "frontdoor.step",
              "frontdoor.drain": "frontdoor.step",
              "request.queued": None, "request.staged": None}
    for name, _, _, p, _ in ev:
        got = ev[p][0] if p is not None else None
        assert got == parent.get(name, "frontdoor.drain"), name
    assert len([e for e in ev if e[0] == "frontdoor.step"]) == \
        fd_on.metrics.steps
    for kind in ("request.queued", "request.staged"):
        assert sorted(e[4] for e in ev if e[0] == kind) == list(range(14))
    assert rec.counters == {"plane_bytes": fd_on.engine.stats.bytes_moved}


@pytest.fixture(scope="module")
def tiny_lm():
    jax = pytest.importorskip("jax")
    from repro.configs import get
    from repro.configs.base import RunConfig, reduced
    from repro.models import init_lm
    cfg = reduced(get("gemma2-2b"), n_layers=2, d_model=64, n_heads=2,
                  n_kv_heads=1, d_ff=128, vocab=64)
    return cfg, RunConfig(kernels="xla", dtype="float32", remat=False), \
        init_lm(jax.random.PRNGKey(1), cfg)


def test_steplm_reads_the_device_twice_per_sampled_token(
        tiny_lm, default_recorder):
    from repro.serve.sched import StepLM
    cfg, rcfg, params = tiny_lm
    rec = default_recorder
    layout = KVLayout(n_pages=24, page_size=4, n_kv_heads=2, head_dim=4,
                      itemsize=4)
    outs = {}
    for on in (False, True):
        rec.enabled = on
        reqs = [ServeRequest(rid=i, prompt=[3 + i, 5, 7, 9][:2 + i % 3],
                             max_new_tokens=3 + i, temperature=float(i % 2),
                             seed=i) for i in range(4)]
        model = StepLM(cfg, rcfg, params, max_len=32,
                       row_bytes=layout.row_bytes)
        _serve(reqs, model=model, layout=layout, max_running=4)
        rec.enabled = False
        outs[on] = [r.output for r in reqs]
    assert outs[True] == outs[False]
    tokens = sum(len(o) for o in outs[True])
    ev = rec.events
    names = [e[0] for e in ev]
    assert names.count("model.sample") == tokens
    assert names.count("model.sync") == 2 * tokens
    assert names.count("model.prefill") == 4
    for name, _, _, p, rid in ev:
        if name == "model.sync":
            assert ev[p][0] == "model.sample"
        if name in ("model.sample", "model.prefill", "model.unstack"):
            assert rid in range(4)
    assert {"model.stack", "model.decode", "model.unstack"} <= set(names)
