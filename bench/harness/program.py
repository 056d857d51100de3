"""The program's own spans and counters (`repro.obs`), as the benchmark
reads them.

While a `Recorder` is on, each of its spans is two things: an event
``(name, start_ns, end_ns, parent, rid)`` of the recorder, on
`time.perf_counter_ns`, and a host event ``repro.<name>`` in the
profiler's trace, on the device trace's clock.  The reductions below
take plain tuples, so that a test can feed them a small synthetic trace:

  program spans   (name, start_ns, end_ns)  ``repro.<name>`` host events
  recorder events (name, start_ns, end_ns, parent, rid)
  counters        {name: int}

`bench/run.py` does not turn the recorder on yet; PERF.md §7 lists the
edits of the harness that would.  `load_spans` repeats the host loop of
`trace.load`, and `innermost` / `idle_by_span` the pass of
`trace.activity` / `idle_by_activity`, for another prefix and any span
name; the same edits would merge each pair.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness.trace import Interval, clip, gaps
from repro.obs import ANNOTATION_PREFIX, Event

NONE = "none"
# the front door's own work on the descriptor plane, per step
PLANE = ("frontdoor.stage", "frontdoor.build", "frontdoor.drain")
# the same, with the engine's phases inside the drains, as innermost spans
PLANE_IDLE = PLANE + ("engine.lower", "engine.simulate", "engine.execute",
                      "engine.deliver")


def load_spans(log_dir: str) -> List[Interval]:
    """The program's spans in the newest ``.xplane.pb`` under
    ``log_dir``, on the trace's clock."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    out.append((e.name[len(ANNOTATION_PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def innermost(spans: Sequence[Interval]) -> List[Interval]:
    """The host timeline as disjoint (span, start, end): the innermost
    open span at each moment (the latest begun; of two begun together,
    the shorter), ``none`` between."""
    spans = [x for x in spans if x[2] > x[1]]
    edges = sorted([(s, 1, s - e, i) for i, (_, s, e) in enumerate(spans)]
                   + [(e, 0, 0, i) for i, (_, _, e) in enumerate(spans)])
    out: List[Interval] = []
    stack: List[int] = []
    last = None
    for t, is_start, _, i in edges:
        if last is not None and t > last:
            out.append((spans[stack[-1]][0] if stack else NONE, last, t))
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
        last = t
    return out


def idle_by_span(ops: Sequence[Interval], spans: Sequence[Interval],
                 t0: float, t1: float) -> List[Tuple[str, float]]:
    """Device idle seconds in [t0, t1] by the innermost program span open
    on the host, ``none`` where none is; longest first.  The seconds add
    up to the window's idle time."""
    timeline = innermost(clip(spans, t0, t1))
    totals: Dict[str, float] = {}
    j = 0
    for gs, ge in gaps(ops, t0, t1):
        covered = 0.0
        while j < len(timeline) and timeline[j][2] <= gs:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][1] < ge:
            name, s, e = timeline[k]
            o = min(e, ge) - max(s, gs)
            if o > 0:
                totals[name] = totals.get(name, 0.0) + o
                covered += o
            k += 1
        if ge - gs - covered > 0:
            totals[NONE] = totals.get(NONE, 0.0) + (ge - gs - covered)
    return sorted(((k, v / 1e9) for k, v in totals.items()),
                  key=lambda kv: -kv[1])


def plane_idle_share(idle: Sequence[Tuple[str, float]], t0: float,
                     t1: float) -> float:
    """Percent of the window [t0, t1] (ns) in which the device was idle
    while the host was on the descriptor plane (``idle_by_span``'s
    seconds)."""
    return 100.0 * sum(s for n, s in idle if n in PLANE_IDLE) \
        / ((t1 - t0) / 1e9)


# -- the recorder's events and counters ---------------------------------------

def durations_ns(events: Sequence[Event], name: str) -> List[int]:
    return [e - s for n, s, e, _, _ in events if n == name]


def span_table(events: Sequence[Event]) -> Dict[str, Tuple[int, float,
                                                           float]]:
    """{name: (count, total seconds, self seconds)}; a span's self time
    leaves out the spans whose parent it is."""
    child_ns = [0] * len(events)
    for _, s, e, parent, _ in events:
        if parent is not None:
            child_ns[parent] += e - s
    out: Dict[str, List[float]] = {}
    for i, (n, s, e, _, _) in enumerate(events):
        row = out.setdefault(n, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e9
        row[2] += (e - s - child_ns[i]) / 1e9
    return {n: tuple(r) for n, r in out.items()}


def _mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def plane_ms_per_step(events: Sequence[Event]) -> Optional[float]:
    """Host milliseconds per front-door step in KV staging, descriptor
    building and the drains."""
    steps = len(durations_ns(events, "frontdoor.step"))
    if not steps:
        return None
    return sum(sum(durations_ns(events, n)) for n in PLANE) / steps / 1e6


def plane_bytes_per_step(events: Sequence[Event],
                         counters: Dict[str, int]) -> Optional[float]:
    """Megabytes the descriptor plane moved per front-door step."""
    steps = len(durations_ns(events, "frontdoor.step"))
    if not steps or "plane_bytes" not in counters:
        return None
    return counters["plane_bytes"] / steps / 1e6


def host_syncs_per_token(events: Sequence[Event]) -> Optional[float]:
    """Blocking device→host reads (``model.sync``) per sampled token
    (``model.sample``)."""
    rows = len(durations_ns(events, "model.sample"))
    return len(durations_ns(events, "model.sync")) / rows if rows \
        else None


def queue_wait_ms_mean(events: Sequence[Event]) -> Optional[float]:
    """Mean milliseconds from submission to admission, over the requests
    submitted and admitted while the recorder was on."""
    w = _mean(durations_ns(events, "request.queued"))
    return None if w is None else w / 1e6


def ttft_split_ms(events: Sequence[Event]) -> Dict[str, Optional[float]]:
    """The mean parts of the time to first token, in milliseconds: queue
    wait, admission to the first decode row (prefill, and the prompt's
    KV rows staged on the plane), and the first token's sampling."""
    staged = {rid for n, _, _, _, rid in events if n == "request.staged"}
    first: Dict[int, int] = {}
    for n, s, e, _, rid in events:
        if n == "model.sample" and rid in staged and rid not in first:
            first[rid] = e - s
    means = (_mean(durations_ns(events, "request.queued")),
             _mean(durations_ns(events, "request.staged")),
             _mean(list(first.values())))
    return {k: None if v is None else v / 1e6 for k, v in
            zip(("queued", "staged", "first_sample"), means)}
