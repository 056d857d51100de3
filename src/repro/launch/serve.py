"""Serving driver: the continuous-batching front door (`ServeFrontDoor`)
over the model's jitted prefill and decode steps (`StepLM`).

By default it serves the named config at its published width on a TPU:
Pallas kernels, bfloat16 compute, bfloat16 parameters made from
``--seed``.  It refuses to start on any other device.  ``--reduced``
serves a tiny same-family config through the XLA reference path in
float32, which runs on the CPU (tests, CPU rehearsals).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \\
      --prompt-lens 16,24 --new-tokens 8

The printed JSON is host wall-clock, labelled with the device it ran on;
it is not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get
from repro.configs.base import ArchConfig, RunConfig, reduced as reduce_cfg
from repro.kernels.runtime import on_tpu
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_lm_params
from repro.serve.kvcache import KVLayout
from repro.serve.sched import ServeFrontDoor, ServeRequest, StepLM

PAGE_SIZE = 16          # tokens per KV block
PREFILL_CHUNK = 64      # prompt rows appended per scheduler step


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config, XLA path, float32 (CPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", default="64,128,256",
                    help="comma-separated prompt lengths, used in turn")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="odd-numbered requests sample at this "
                         "temperature; even-numbered ones are greedy")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run_configs(arch: str, reduced: bool) -> Tuple[ArchConfig, RunConfig]:
    if reduced:
        return reduce_cfg(get(arch)), RunConfig(
            kernels="xla", dtype="float32", remat=False)
    return get(arch), RunConfig(kernels="pallas", dtype="bfloat16",
                                param_dtype="bfloat16", remat=False)


def load(args: argparse.Namespace):
    """(cfg, rcfg, params) for ``args``; parameters come from --seed."""
    cfg, rcfg = run_configs(args.arch, args.reduced)
    if rcfg.kernels == "pallas" and not on_tpu():
        raise SystemExit(
            f"{cfg.name} at full width runs the Pallas kernels and needs a "
            f"TPU, found {jax.devices()[0].platform}; --reduced serves a "
            f"tiny config on this device")
    return cfg, rcfg, init_lm_params(jax.random.PRNGKey(args.seed), cfg,
                                     rcfg)


def make_requests(cfg: ArchConfig, args: argparse.Namespace
                  ) -> List[ServeRequest]:
    """``args.requests`` prompts of random tokens from --seed, their
    lengths taken in turn from --prompt-lens."""
    lens = [int(n) for n in args.prompt_lens.split(",")]
    rng = np.random.default_rng(args.seed)
    return [ServeRequest(
        rid=i,
        prompt=[int(t) for t in rng.integers(0, cfg.vocab_size,
                                             lens[i % len(lens)])],
        max_new_tokens=args.new_tokens,
        temperature=args.temperature if i % 2 else 0.0,
        seed=args.seed + i) for i in range(args.requests)]


def kv_layout(cfg: ArchConfig, rcfg: RunConfig, n_pages: int,
              page_size: int) -> KVLayout:
    """The descriptor plane's page geometry: one token's K (or V) row is
    the model's KV heads × head width in the cache dtype (the compute
    dtype)."""
    return KVLayout(n_pages, page_size, cfg.n_kv_heads,
                    cfg.resolved_head_dim,
                    itemsize=np.dtype(rcfg.dtype).itemsize)


def serve(cfg: ArchConfig, rcfg: RunConfig, params,
          reqs: List[ServeRequest], seed: int = 0) -> Dict:
    """Serve ``reqs`` to completion through one front door; the pool
    holds every request at full length.  Raises if a block leaks."""
    longest = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    max_len = -(-longest // PAGE_SIZE) * PAGE_SIZE
    layout = kv_layout(cfg, rcfg, len(reqs) * (max_len // PAGE_SIZE),
                       PAGE_SIZE)
    model = StepLM(cfg, rcfg, params, max_len=max_len,
                   row_bytes=layout.row_bytes, seed=seed)
    fd = ServeFrontDoor(model, layout, max_seq_len=max_len,
                        prefill_chunk=PREFILL_CHUNK)
    for r in reqs:
        fd.submit(r)
    t0 = time.perf_counter()
    metrics = fd.run()
    wall = time.perf_counter() - t0
    dev = jax.devices()[0]
    return {
        "arch": cfg.name,
        "kernels": rcfg.kernels,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "requests": len(reqs),
        "new_tokens": sum(len(r.output) for r in reqs),
        "steps": metrics.steps,
        "leaked_blocks": len(fd.alloc.leaked()),
        "host_wall_s": wall,
        "sample_output": reqs[0].output[:8],
    }


def run(args: argparse.Namespace) -> Dict:
    cfg, rcfg, params = load(args)
    return serve(cfg, rcfg, params, make_requests(cfg, args), args.seed)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    print(json.dumps(run(args), indent=1))


if __name__ == "__main__":
    main()
