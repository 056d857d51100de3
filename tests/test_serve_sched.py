"""Continuous-batching serve scheduler tests: allocator invariants,
descriptor builders, exhaustion → preemption → swap byte-identity,
refcount churn, irq-vs-poll equivalence, and the jax `StepLM` binding.
"""

import numpy as np
import pytest

from repro.core import Protocol
from repro.serve.kvcache import (KVLayout, span_append_descriptors,
                                 swap_descriptors)
from repro.serve.sched import (BlockAllocator, HashLM, ReqState,
                               ServeFrontDoor, ServeRequest,
                               oracle_generate)

LAYOUT = KVLayout(n_pages=24, page_size=4, n_kv_heads=2, head_dim=4,
                  itemsize=4)  # row 32 B, page 128 B


def _requests(n, seed=0, vocab=64, max_prompt=12, max_new=10):
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(2, max_prompt + 1))
        reqs.append(ServeRequest(
            rid=rid,
            prompt=list(map(int, rng.integers(0, vocab, plen))),
            max_new_tokens=int(rng.integers(2, max_new + 1)),
            temperature=float(rng.choice([0.0, 0.8])),
            seed=int(rng.integers(0, 1 << 31))))
    return reqs


def _run_front(reqs, layout=LAYOUT, gap=0, **kw):
    model = HashLM(layout.row_bytes)
    kw.setdefault("max_seq_len", 24)
    fd = ServeFrontDoor(model, layout, **kw)
    for i, r in enumerate(reqs):
        fd.submit(r, at_cycle=i * gap)
    fd.run()
    return fd, model


class TestBlockAllocator:
    def test_alloc_free_refcount(self):
        a = BlockAllocator(8)
        blocks = a.alloc(3)
        assert len(set(blocks)) == 3 and a.used_blocks == 3
        a.incref([blocks[0]])
        a.decref([blocks[0]])
        assert a.used_blocks == 3           # still referenced once
        a.decref(blocks)
        assert a.used_blocks == 0 and a.free_blocks == 8
        a.check()

    def test_exhaustion_and_watermark(self):
        a = BlockAllocator(8, low_watermark=2)
        assert a.can_alloc(8) and not a.can_alloc(9)
        assert a.above_watermark(6) and not a.above_watermark(7)
        with pytest.raises(MemoryError):
            a.alloc(9)
        assert a.stats.failures == 1

    def test_swap_slots_and_leak_detection(self):
        a = BlockAllocator(4, n_swap_slots=2)
        blocks = a.alloc(2)
        slots = a.alloc_swap(2)
        assert not a.can_alloc_swap(1)
        assert sorted(a.leaked()) == sorted(blocks)
        a.free_swap(slots)
        a.decref(blocks)
        assert a.leaked() == []
        a.check()

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        a.decref([b])
        with pytest.raises(ValueError):
            a.decref([b])


class TestDescriptorBuilders:
    def test_span_append_addresses(self):
        lay = LAYOUT
        batch = span_append_descriptors(lay, [5, 2], 3, 6,
                                        stage_k=100, stage_v=200)
        # positions 3..5 → (page 0, slot 3), (page 1, slots 0..1)
        k_dst = [5 * lay.page_bytes + 3 * lay.row_bytes,
                 2 * lay.page_bytes, 2 * lay.page_bytes + lay.row_bytes]
        v_dst = [lay.pool_bytes + d for d in k_dst]
        assert batch.dst_addr.tolist() == k_dst + v_dst
        assert batch.src_addr.tolist()[:3] == \
            [100, 100 + lay.row_bytes, 100 + 2 * lay.row_bytes]
        assert set(batch.length.tolist()) == {lay.row_bytes}
        assert batch.row(0).src_protocol == Protocol.VMEM
        assert batch.row(0).dst_protocol == Protocol.HBM

    def test_swap_round_trip_addresses(self):
        lay = LAYOUT
        out = swap_descriptors(lay, [3, 7], [1, 0], "out")
        back = swap_descriptors(lay, [3, 7], [1, 0], "in")
        assert out.src_addr.tolist() == back.dst_addr.tolist()
        assert out.dst_addr.tolist() == back.src_addr.tolist()
        pb = lay.page_bytes
        assert out.dst_addr.tolist() == [2 * pb, 0, 3 * pb, pb]
        with pytest.raises(ValueError):
            swap_descriptors(lay, [1, 2], [0], "out")
        with pytest.raises(ValueError):
            swap_descriptors(lay, [1], [0], "sideways")


class TestFrontDoor:
    def test_oracle_identity_no_pressure(self):
        reqs = _requests(8, seed=1)
        fd, model = _run_front(reqs, max_running=8)
        assert fd.alloc.stats.preemptions == 0
        for r in reqs:
            assert r.output == oracle_generate(
                model, r.seed, r.prompt, r.max_new_tokens,
                r.temperature, r.stop_tokens), f"rid {r.rid}"

    def test_preemption_swap_byte_identity(self):
        """Exhaustion → preemption → swap-out/in must be invisible in
        the tokens: a starved pool run equals the oracle (and therefore
        equals an uncontended big-pool run)."""
        small = KVLayout(n_pages=10, page_size=4, n_kv_heads=2,
                         head_dim=4, itemsize=4)
        reqs = _requests(14, seed=2)
        fd, model = _run_front(reqs, layout=small, max_running=6,
                               low_watermark=1, sanitize=True)
        assert fd.alloc.stats.preemptions > 0
        assert fd.alloc.stats.swapped_out == fd.alloc.stats.swapped_in > 0
        for r in reqs:
            assert r.output == oracle_generate(
                model, r.seed, r.prompt, r.max_new_tokens,
                r.temperature, r.stop_tokens), f"rid {r.rid}"

    def test_irq_equals_poll(self):
        """Interrupt-driven and register-poll completion drive the
        identical schedule: same tokens, same steps, same preemption and
        swap counts, same simulated cycles."""
        runs = {}
        for mode in ("irq", "poll"):
            small = KVLayout(n_pages=10, page_size=4, n_kv_heads=2,
                             head_dim=4, itemsize=4)
            reqs = _requests(14, seed=3)
            fd, _ = _run_front(reqs, layout=small, max_running=6,
                               low_watermark=1, completion=mode)
            runs[mode] = ([r.output for r in reqs], fd.metrics.steps,
                          fd.metrics.cycles, fd.alloc.stats.preemptions,
                          fd.alloc.stats.swapped_out)
        assert runs["irq"] == runs["poll"]
        assert runs["irq"][3] > 0           # pressure actually happened

    def test_churn_leaks_nothing(self):
        """1k requests through a starved pool: every block and swap slot
        back on the free lists, refcounts clean."""
        small = KVLayout(n_pages=10, page_size=4, n_kv_heads=2,
                         head_dim=4, itemsize=4)
        reqs = _requests(1000, seed=4, max_prompt=10, max_new=6)
        fd, _ = _run_front(reqs, layout=small, max_running=6,
                           low_watermark=1, gap=300)
        assert fd.alloc.stats.preemptions > 0
        # check_drained() already ran inside run(); make the gate explicit
        assert fd.alloc.leaked() == []
        assert fd.alloc.free_blocks == fd.alloc.n_blocks
        assert fd.alloc.free_swap_slots == fd.alloc.n_swap_slots
        fd.alloc.check()

    def test_eos_and_stop_tokens_release_blocks(self):
        model = HashLM(LAYOUT.row_bytes)
        fd = ServeFrontDoor(model, LAYOUT, max_seq_len=24)
        # seed chosen so greedy emits eos quickly is fiddly; use stop set
        # covering half the vocab so stops fire fast
        stops = tuple(range(32))
        reqs = [ServeRequest(rid=i, prompt=[i + 2, 5], max_new_tokens=20,
                             stop_tokens=stops, seed=i) for i in range(4)]
        for r in reqs:
            fd.submit(r)
        fd.run()
        assert any(len(r.output) < r.max_new_tokens for r in reqs)
        for r in reqs:
            assert r.output == oracle_generate(model, r.seed, r.prompt,
                                               r.max_new_tokens, 0.0,
                                               stops)
            assert r.state is ReqState.FINISHED and r.blocks == []

    def test_submit_rejects_oversize(self):
        model = HashLM(LAYOUT.row_bytes)
        fd = ServeFrontDoor(model, LAYOUT, max_seq_len=16)
        with pytest.raises(ValueError):
            fd.submit(ServeRequest(rid=0, prompt=[1] * 10,
                                   max_new_tokens=10))

    def test_plan_cache_reuse(self):
        reqs = _requests(12, seed=5)
        fd, _ = _run_front(reqs, max_running=8)
        assert fd.plan_cache.stats.hit_rate > 0.5


class TestHashLM:
    def test_rows_deterministic_and_positional(self):
        m = HashLM(32)
        a = m.kv_rows(7, [1, 2, 3], 0, 3, "k")
        b = m.kv_rows(7, [1, 2, 3], 0, 3, "k")
        assert np.array_equal(a, b)
        assert not np.array_equal(a[0], a[1])          # position-keyed
        assert not np.array_equal(a, m.kv_rows(7, [1, 2, 3], 0, 3, "v"))
        assert not np.array_equal(a, m.kv_rows(8, [1, 2, 3], 0, 3, "k"))
        # suffix rows don't depend on how much history was materialized
        assert np.array_equal(m.kv_rows(7, [1, 2, 3], 2, 3, "k"), a[2:])

    def test_digest_sensitive_to_any_byte(self):
        m = HashLM(32)
        kb = m.kv_rows(1, [4, 5], 0, 2, "k").reshape(-1)
        vb = m.kv_rows(1, [4, 5], 0, 2, "v").reshape(-1)
        req = type("R", (), {"seed": 1, "tokens": [4, 5],
                             "temperature": 0.0})()
        base = m.next_tokens([req], [(kb, vb)])[0]
        flip = kb.copy()
        flip[17] ^= 1
        assert m.next_tokens([req], [(flip, vb)])[0] != base


class TestServeEngineSampling:
    """Satellites 1 & 2: per-request temperatures and stop tokens in the
    padded-batch `ServeEngine`."""

    @pytest.fixture(scope="class")
    def engine(self):
        jax = pytest.importorskip("jax")
        from repro.configs import get
        from repro.configs.base import RunConfig, reduced
        from repro.models import init_lm
        from repro.serve import ServeEngine
        cfg = reduced(get("gemma2-2b"), n_layers=2, d_model=64,
                      n_heads=2, n_kv_heads=1, d_ff=128, vocab=128)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        rcfg = RunConfig(kernels="xla", dtype="float32", remat=False)
        return ServeEngine(cfg, rcfg, params, max_len=64)

    def test_greedy_rows_unpolluted_by_hot_neighbours(self, engine):
        from repro.serve import Request
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        pure = engine.generate([Request(prompt=list(prompt),
                                        max_new_tokens=6)])
        mixed = engine.generate([
            Request(prompt=list(prompt), max_new_tokens=6),
            Request(prompt=list(prompt), max_new_tokens=6,
                    temperature=1.3),
        ])
        assert mixed[0].output == pure[0].output
        assert len(mixed[1].output) == 6

    def test_stop_tokens_end_generation_early(self, engine):
        from repro.serve import Request
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        full = engine.generate([Request(prompt=list(prompt),
                                        max_new_tokens=8)])[0]
        stop = full.output[2]
        stopped = engine.generate([Request(prompt=list(prompt),
                                           max_new_tokens=8,
                                           stop_tokens=(stop,))])[0]
        assert stopped.finished
        # generation ends at the FIRST occurrence of the stop token
        # (inclusive), which may be earlier than where we sampled it
        first = full.output.index(stop)
        assert stopped.output == full.output[:first + 1]
        assert len(stopped.output) < len(full.output)


class TestStepLM:
    def test_continuous_equals_sequential(self):
        jax = pytest.importorskip("jax")
        from repro.configs import get
        from repro.configs.base import RunConfig, reduced
        from repro.models import init_lm
        from repro.serve.sched import StepLM
        cfg = reduced(get("gemma2-2b"), n_layers=2, d_model=64,
                      n_heads=2, n_kv_heads=1, d_ff=128, vocab=64)
        params = init_lm(jax.random.PRNGKey(1), cfg)
        rcfg = RunConfig(kernels="xla", dtype="float32", remat=False)

        def make_reqs():
            rng = np.random.default_rng(9)
            return [ServeRequest(
                rid=i, prompt=list(map(int, rng.integers(2, 60, 4 + i))),
                max_new_tokens=4, temperature=float(i % 2), seed=i)
                for i in range(4)]

        def run(reqs, max_running):
            model = StepLM(cfg, rcfg, params, max_len=32,
                           row_bytes=LAYOUT.row_bytes)
            fd = ServeFrontDoor(model, LAYOUT, max_seq_len=16,
                                max_running=max_running)
            for r in reqs:
                fd.submit(r)
            fd.run()
            return [r.output for r in reqs]

        batched = run(make_reqs(), max_running=4)
        solo = run(make_reqs(), max_running=1)
        assert batched == solo

    def test_nonfinite_logits_raise(self):
        jax = pytest.importorskip("jax")
        from repro.configs import get
        from repro.configs.base import RunConfig, reduced
        from repro.models import init_lm
        from repro.serve.sched import StepLM
        cfg = reduced(get("gemma2-2b"), n_layers=2, d_model=64,
                      n_heads=2, n_kv_heads=1, d_ff=128, vocab=64)
        params = init_lm(jax.random.PRNGKey(1), cfg)
        params["final_norm"]["scale"] = params["final_norm"]["scale"] * \
            float("nan")
        model = StepLM(cfg, RunConfig(kernels="xla", dtype="float32"),
                       params, max_len=32, row_bytes=LAYOUT.row_bytes)
        fd = ServeFrontDoor(model, LAYOUT, max_seq_len=16)
        fd.submit(ServeRequest(rid=0, prompt=[3, 4, 5], max_new_tokens=2))
        with pytest.raises(FloatingPointError, match="non-finite"):
            fd.run()


class TestServeLauncher:
    """`repro.launch.serve`: the front door + `StepLM` entry point."""

    def test_reduced_serves_every_token_without_leaks(self):
        from repro.launch.serve import parse_args, run
        report = run(parse_args([
            "--reduced", "--requests", "4", "--prompt-lens", "9,70",
            "--new-tokens", "5"]))
        assert report["kernels"] == "xla"
        assert report["device"]["platform"] == "cpu"
        assert report["requests"] == 4
        assert report["new_tokens"] == 4 * 5
        assert report["leaked_blocks"] == 0

    def test_kv_layout_is_the_model_geometry(self):
        from repro.configs import get
        from repro.launch.serve import kv_layout, run_configs
        cfg, rcfg = run_configs("gemma2-2b", reduced=False)
        assert rcfg.kernels == "pallas" and rcfg.param_dtype == "bfloat16"
        lay = kv_layout(cfg, rcfg, n_pages=8, page_size=16)
        # 4 KV heads x head_dim 256 x 2-byte bfloat16
        assert lay.row_bytes == 4 * 256 * 2 == 2048
        assert cfg == get("gemma2-2b")

    def test_full_width_refuses_a_cpu(self):
        from repro.launch.serve import load, parse_args
        with pytest.raises(SystemExit, match="needs a TPU"):
            load(parse_args(["--arch", "gemma2-2b"]))

    def test_params_are_stored_in_param_dtype(self):
        import jax
        import jax.numpy as jnp
        from repro.configs import get
        from repro.configs.base import RunConfig, reduced
        from repro.models import init_lm_params
        cfg = reduced(get("gemma2-2b"))
        params = init_lm_params(jax.random.PRNGKey(0), cfg,
                                RunConfig(param_dtype="bfloat16"))
        dtypes = {a.dtype for a in jax.tree_util.tree_leaves(params)}
        assert dtypes == {jnp.dtype(jnp.bfloat16)}

    def test_same_seed_same_weights_in_every_process(self, subproc):
        import jax
        from repro.models.common import fold_keys
        code = """
            import jax
            from repro.models.common import fold_keys
            print([k.tolist() for k in fold_keys(jax.random.PRNGKey(3),
                                                 "embed", "layers")])
        """
        here = [k.tolist() for k in fold_keys(jax.random.PRNGKey(3),
                                              "embed", "layers")]
        # the child hashes strings with another random seed
        assert subproc(code, n_devices=1).strip() == str(here)
