"""Smoke test of the serving path on one TPU chip.

    python chip_smoke.py [--seed N]

One process, four phases, in order; any failure ends it with a non-zero
exit and no result line:

1. compile cache: ``repro.launch.compile_cache`` places JAX's persistent
   cache before anything compiles (``JAX_COMPILATION_CACHE_DIR`` if set,
   else ``.jax_cache/`` in the checkout);
2. device: the default device must be a TPU.  There is no CPU fallback;
3. kernels: every main-path Pallas kernel compiled (``interpret=False``)
   at the widths the models use and run once against its ``ref.py``
   oracle;
4. model: gemma2-2b at its published width, bfloat16 parameters made
   from ``--seed``, serves 8 requests of 32 new tokens through
   ``repro.launch.serve`` (``ServeFrontDoor`` + ``StepLM``), twice: cold,
   then warm.  Then two prompts' prefill and first decode logits are
   compared between the Pallas kernels and the XLA reference path on
   the same parameters.

The lines before the last report what ran and on which device; their
times are host wall-clock of a smoke run, not benchmark numbers.  The
last line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# bfloat16 inputs: max |kernel - oracle| over max |oracle|
BF16_TOL = 2e-2
# float32 inputs, with the chip's default matmul precision on both sides
F32_TOL = 1e-2


class Counters:
    """Compilations and persistent-cache hits, from JAX's own events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self, jax) -> None:
        self.counts = {self.COMPILE: 0, self.HIT: 0, self.MISS: 0}
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_: self._count(event))
        jax.monitoring.register_event_listener(
            lambda event, **_: self._count(event))

    def _count(self, event: str) -> None:
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self):
        """(compilations, cache hits, cache misses) so far."""
        return tuple(self.counts[e] for e in (self.COMPILE, self.HIT,
                                             self.MISS))


def check(name: str, got, want, tol: float) -> None:
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if g.shape != w.shape:
        raise AssertionError(f"{name}: shape {g.shape} != {w.shape}")
    err = float(np.max(np.abs(g - w))) / max(float(np.max(np.abs(w))), 1e-6)
    print(f"kernel {name}: max_rel_err={err:.3e} tol={tol:.0e}", flush=True)
    if not err <= tol:
        raise AssertionError(f"{name}: error {err} above {tol}")


def exact(name: str, got, want) -> None:
    import numpy as np
    if not np.array_equal(np.asarray(got), np.asarray(want)):
        raise AssertionError(f"{name}: differs from its oracle")
    print(f"kernel {name}: bit-exact", flush=True)


def kernels_phase(jax, jnp) -> None:
    from repro.kernels.copy_engine import copy_2d, strided_copy_nd
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.init_engine import (iota_fill, iota_fill_ref, memset,
                                           memset_ref, prng_fill,
                                           prng_fill_ref)
    from repro.kernels.matmul_dma import matmul, matmul_ref
    from repro.kernels.ssd import ssd, ssd_ref

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    # gemma2-2b attention: 8 query heads over 4 KV heads of width 256
    hq, hkv, dh = 8, 4, 256
    for S, kv_len, window, cap in ((4096, 3001, 0, 0.0),
                                   (1032, 1032, 512, 50.0)):
        q = normal((8, hq, dh))
        k, v = normal((8, hkv, S, dh)), normal((8, hkv, S, dh))
        kw = dict(kv_len=jnp.int32(kv_len), window=window, softcap=cap,
                  scale=dh ** -0.5)
        check(f"decode_attention S={S} kv_len={kv_len} window={window} "
              f"softcap={cap}", decode_attention(q, k, v, **kw),
              decode_attention_ref(q, k, v, **kw), BF16_TOL)
    for sq in (37, 512, 1000):
        q = normal((1, hq, sq, dh))
        k, v = normal((1, hkv, sq, dh)), normal((1, hkv, sq, dh))
        kw = dict(causal=True, window=4096, softcap=50.0, scale=dh ** -0.5)
        check(f"flash_attention Sq={sq}", flash_attention(q, k, v, **kw),
              attention_ref(q, k, v, **kw), BF16_TOL)
    for shape, dtype in (((4096, 4096), jnp.bfloat16),
                         ((4096, 4096), jnp.float32),
                         ((1000, 300), jnp.float32)):
        x = normal(shape, dtype)
        exact(f"copy_2d {shape} {jnp.dtype(dtype).name}", copy_2d(x), x)
    x = normal((4, 2, 512, 1024), jnp.float32)
    exact("strided_copy_nd (4, 2, 512, 1024)", strided_copy_nd(x), x)
    shape = (4096, 4096)
    exact("memset bfloat16", memset(shape, 0.5, jnp.bfloat16),
          memset_ref(shape, 0.5, jnp.bfloat16))
    exact("iota_fill int32", iota_fill(shape, 7), iota_fill_ref(shape, 7))
    for dtype in (jnp.float32, jnp.bfloat16, jnp.int8, jnp.uint32):
        exact(f"prng_fill {jnp.dtype(dtype).name}", prng_fill(shape, 11, dtype),
              prng_fill_ref(shape, 11, dtype))
    x, w = normal((2048, 2304)), normal((2304, 9216), scale=2304 ** -0.5)
    check("matmul_dma 2048x2304 @ 2304x9216", matmul(x, w), matmul_ref(x, w),
          BF16_TOL)
    # mamba2-1.3b: 64 heads of width 64, state 128, one group, chunk 256
    H, S, P, N = 64, 2048, 64, 128
    x = normal((1, H, S, P), jnp.float32)
    dt = jax.random.uniform(next(keys), (1, H, S), minval=1e-3, maxval=0.1)
    A = -jax.random.uniform(next(keys), (H,), minval=0.5, maxval=2.0)
    D = normal((H,), jnp.float32)
    Bm = normal((1, 1, S, N), jnp.float32, 0.3)
    Cm = normal((1, 1, S, N), jnp.float32, 0.3)
    check("ssd H=64 S=2048 chunk=256 N=128 P=64",
          ssd(x, dt, A, D, Bm, Cm, chunk=256), ssd_ref(x, dt, A, D, Bm, Cm),
          F32_TOL)


def serve_phase(counters: Counters, seed: int):
    from repro.launch import serve

    args = serve.parse_args(["--arch", "gemma2-2b", "--seed", str(seed)])
    cfg, rcfg, params = serve.load(args)
    for label in ("cold", "warm"):
        reqs = serve.make_requests(cfg, args)
        before = counters.snapshot()
        t0 = time.perf_counter()
        report = serve.serve(cfg, rcfg, params, reqs, args.seed)
        wall = time.perf_counter() - t0
        compiles, hits, misses = (a - b for a, b in
                                  zip(counters.snapshot(), before))
        lens = sorted({len(r.prompt) for r in reqs})
        print(f"serve {label}: {report['requests']} requests, prompt lengths "
              f"{lens}, {report['new_tokens']} new tokens in "
              f"{report['steps']} steps, leaked blocks "
              f"{report['leaked_blocks']}; compilations={compiles} "
              f"(persistent cache hits={hits} misses={misses}); host wall "
              f"{wall:.1f} s", flush=True)
        short = [r.rid for r in reqs if len(r.output) != args.new_tokens]
        if short:
            raise AssertionError(f"requests {short} did not return "
                                 f"{args.new_tokens} tokens")
        if report["leaked_blocks"]:
            raise AssertionError(f"{report['leaked_blocks']} blocks leaked")
    return cfg, rcfg, params, reqs


def agreement_phase(jax, jnp, cfg, rcfg, params, reqs) -> None:
    """Prefill logits at the last prompt position, and the logits of one
    decode step after it, for two prompts of one length: the Pallas
    kernels against the XLA reference path, same parameters."""
    import numpy as np
    from repro.serve.serve_step import make_decode_step, make_prefill_step

    n = len(reqs[0].prompt)
    pair = [r.prompt for r in reqs if len(r.prompt) == n][:2]
    tokens = jnp.asarray(pair, jnp.int32)
    max_len = n + 8
    logits = {}
    nxt = None
    for kernels in ("pallas", "xla"):
        rc = dataclasses.replace(rcfg, kernels=kernels)
        prefill = jax.jit(make_prefill_step(cfg, rc, max_len=max_len))
        decode = jax.jit(make_decode_step(cfg, rc))
        first, caches = prefill(params, tokens)
        if nxt is None:            # both paths decode the same token
            nxt = jnp.argmax(first, axis=-1).astype(jnp.int32)[:, None]
        second, _ = decode(params, caches, nxt, jnp.int32(n))
        logits[kernels] = (np.asarray(first), np.asarray(second))
    for i, step in enumerate(("prefill", "decode")):
        got, want = logits["pallas"][i], logits["xla"][i]
        if not (np.isfinite(got).all() and np.isfinite(want).all()):
            raise AssertionError(f"{step}: non-finite logits")
        err = float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))
        top = np.sort(want, axis=-1)
        margin = float(np.min(top[:, -1] - top[:, -2]))
        same = bool((got.argmax(-1) == want.argmax(-1)).all())
        print(f"agreement {step} (2 prompts of {n} tokens): pallas vs xla "
              f"max_rel_err={err:.3e} tol={BF16_TOL:.0e}, top-1 same={same}"
              f" (xla top-1 margin {margin:.3e})", flush=True)
        if not err <= BF16_TOL or not same:
            raise AssertionError(f"{step}: Pallas and XLA logits disagree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # 1. compile cache, before anything compiles
    cache_dir = enable_compile_cache()
    import jax
    import jax.numpy as jnp
    counters = Counters(jax)
    print(f"compile cache: {cache_dir}", flush=True)

    # 2. device
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (default device is "
                 f"{dev.platform}); this smoke test runs only on a TPU")
    print(f"device: {dev.device_kind}, count {len(jax.devices())}, "
          f"jax {jax.__version__}", flush=True)

    # 3. kernels
    t0 = time.perf_counter()
    kernels_phase(jax, jnp)
    print(f"kernels: all passed in {time.perf_counter() - t0:.1f} s",
          flush=True)

    # 4. model
    cfg, rcfg, params, reqs = serve_phase(counters, args.seed)
    agreement_phase(jax, jnp, cfg, rcfg, params, reqs)
    compiles, hits, misses = counters.snapshot()
    print(f"process: compilations={compiles}, persistent cache hits={hits} "
          f"misses={misses}, peak_bytes_in_use="
          f"{dev.memory_stats()['peak_bytes_in_use']}", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
