"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV.  `python -m benchmarks.run [--only X]`.

Suites are imported lazily so `--only` works even when a heavyweight or
optional dependency of an unrelated suite (jax, repro.dist) is missing.

``--engine <preset>`` sweeps a named `EngineSpec` preset
(`repro.core.spec.PRESETS`: pulp_cluster / manticore / cheshire /
edge_ai) through every suite whose ``run`` accepts an ``engine`` kwarg —
the suite re-runs its measurement on the preset's bundled timing models
(`channel_sweep` is the first adopter).

`--json [PATH]` additionally writes the descriptor-plane perf headline
(object-vs-batch speedup, sweep wall clocks) plus per-suite wall-clock
timings to PATH (default ``BENCH_descriptor_plane.json``), and — unless
``--no-snapshot`` — a numbered ``BENCH_<n>.json`` snapshot at the repo
root (schema: suite name → that suite's ``LAST`` metrics dict, plus a
``_meta`` record) so the perf trajectory is tracked across PRs.  ``<n>``
auto-increments past the highest existing snapshot; pin it with
``--snapshot N``.  Partial runs (``--only``) skip the numbered snapshot
unless an index is pinned explicitly.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import re
import sys
import time

SUITES = [
    ("bus_utilization", "Fig. 8 + §3.1"),
    ("outstanding_sweep", "Fig. 14"),
    ("area_model", "Table 4 / Fig. 12"),
    ("timing_model", "Fig. 13"),
    ("latency", "§4.3"),
    ("workload_speedup", "§3.4 / §3.5 (Fig. 11)"),
    ("descriptor_plane", "SoA vs object descriptor hot path"),
    ("dataplane", "vectorized functional data plane (execute_batch)"),
    ("sanitize", "static hazard sweep throughput vs execute_batch"),
    ("channel_sweep", "multi-channel aggregate bandwidth (§4 concurrency)"),
    ("plan_replay", "compile-once / replay-many paged-KV decode"),
    ("vm_translate", "virtual-memory translation overhead (TLB-warm)"),
    ("serve_bench", "continuous batching vs padded batch (closed loop)"),
    ("collective_sweep", "multi-engine collective fabric scaling"),
    ("kernel_bench", "kernels + TPU rooflines"),
    ("roofline", "dry-run roofline table"),
]

#: suite name → module (descriptor_plane lives in descriptor_plane_bench)
_MODULES = {name: f"benchmarks.{name}" for name, _ in SUITES}
_MODULES["descriptor_plane"] = "benchmarks.descriptor_plane_bench"
_MODULES["dataplane"] = "benchmarks.dataplane_bench"
_MODULES["sanitize"] = "benchmarks.sanitize_bench"
_MODULES["plan_replay"] = "benchmarks.plan_replay_bench"


#: repo root — numbered snapshots always land here (not the cwd), so the
#: cross-PR trajectory keeps one consistent numbering
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _next_snapshot_index(root: str = _REPO_ROOT) -> int:
    """1 + the highest existing BENCH_<n>.json index at the repo root."""
    best = 0
    for name in os.listdir(root):
        m = re.fullmatch(r"BENCH_(\d+)\.json", name)
        if m:
            best = max(best, int(m.group(1)))
    return best + 1


def write_snapshot(suite_metrics, wall, errors, index=None,
                   skipped=None) -> str:
    """Write the numbered perf-trajectory snapshot (suite → metrics)."""
    if index is None:
        index = _next_snapshot_index()
    payload = dict(suite_metrics)
    payload["_meta"] = {
        "index": index,
        "suite_wall_clock_s": wall,
        **({"suite_errors": errors} if errors else {}),
        **({"suite_skipped": skipped} if skipped else {}),
    }
    path = os.path.join(_REPO_ROOT, f"BENCH_{index}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", nargs="?", const="BENCH_descriptor_plane.json",
                    default=None, metavar="PATH",
                    help="write descriptor-plane perf + suite wall clocks")
    ap.add_argument("--snapshot", type=int, default=None, metavar="N",
                    help="pin the BENCH_<n>.json snapshot index")
    ap.add_argument("--no-snapshot", action="store_true",
                    help="skip the numbered BENCH_<n>.json snapshot")
    ap.add_argument("--engine", default=None, metavar="PRESET",
                    help="sweep a named EngineSpec preset (repro.core.spec"
                         ".PRESETS) in the suites that support it")
    ap.add_argument("--quick", action="store_true",
                    help="shrink the heavyweight suites (dataplane, "
                         "descriptor plane) to smoke-test sizes with "
                         "relaxed gates; implies --no-snapshot")
    args = ap.parse_args()

    if args.engine is not None:
        from repro.core.spec import PRESETS
        if args.engine not in PRESETS:
            ap.error(f"unknown --engine preset {args.engine!r}: expected "
                     f"one of {sorted(PRESETS)}")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []
    wall = {}
    errors = {}
    skipped = {}
    for name, what in SUITES:
        if args.only and args.only != name:
            continue
        print(f"# suite: {name} ({what})", file=sys.stderr)
        t0 = time.perf_counter()
        n_rows_before = len(rows)
        try:
            mod = importlib.import_module(_MODULES[name])
            # suites opt into preset sweeps / quick mode by kwarg
            params = inspect.signature(mod.run).parameters
            kwargs = {}
            if args.engine is not None and "engine" in params:
                kwargs["engine"] = args.engine
            if args.quick and "quick" in params:
                kwargs["quick"] = True
            mod.run(rows, **kwargs)
            wall[name] = time.perf_counter() - t0
        except ModuleNotFoundError as err:
            # a missing *optional* dependency (jax on a CPU box,
            # repro.dist before the distributed layer lands) is not a
            # broken suite: record the skip, keep the exit code green
            if args.only:
                raise
            skipped[name] = f"missing dependency: {err.name}"
            del rows[n_rows_before:]   # skipped means *no* partial rows
            print(f"# suite {name} SKIPPED ({skipped[name]})",
                  file=sys.stderr)
        except Exception as err:
            # a broken suite must not discard the rows and timings every
            # suite before it already measured
            if args.only:
                raise
            errors[name] = f"{type(err).__name__}: {err}"
            print(f"# suite {name} FAILED: {errors[name]}", file=sys.stderr)
    print("name,value,derived")
    for name, value, derived in rows:
        if isinstance(value, float):
            value = f"{value:.6g}"
        print(f"{name},{value},{derived}")

    if args.json:
        payload = {"suite_wall_clock_s": wall}
        if errors:
            payload["suite_errors"] = errors
        if skipped:
            payload["suite_skipped"] = skipped
        # persist any suite's module-level LAST dict (partial data survives
        # a failed gate; import-time failures are already in suite_errors)
        suite_metrics = {}
        for name in sorted(set(wall) | set(errors)):
            try:
                last = getattr(importlib.import_module(_MODULES[name]),
                               "LAST", None)
                if last:
                    suite_metrics[name] = dict(last)
            except Exception:
                pass
        payload.update(suite_metrics)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", file=sys.stderr)
        # numbered trajectory snapshots only make sense for full runs —
        # a partial --only or shrunk --quick run would mint an index whose
        # metrics are not comparable to the committed full-run snapshots
        if not args.no_snapshot and not args.quick and \
                (args.only is None or args.snapshot is not None):
            snap = write_snapshot(suite_metrics, wall, errors,
                                  index=args.snapshot, skipped=skipped)
            print(f"# wrote {snap}", file=sys.stderr)

    if errors:
        sys.exit(1)        # after persisting partial results


if __name__ == "__main__":
    main()
