"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(42)


def arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def allclose(a, b, dtype=jnp.float32):
    a32 = np.asarray(a, np.float32)
    b32 = np.asarray(b, np.float32)
    denom = max(np.max(np.abs(b32)), 1e-6)
    err = np.max(np.abs(a32 - b32)) / denom
    assert err < TOL[dtype], f"rel err {err}"


class TestCopyEngine:
    @pytest.mark.parametrize("shape", [(8, 128), (100, 300), (512, 1024)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_copy_2d(self, shape, dtype):
        from repro.kernels.copy_engine import copy_2d, copy_2d_ref
        x = arr(shape, dtype)
        y = copy_2d(x, backend="pallas", interpret=True)
        allclose(y, copy_2d_ref(x), dtype)

    def test_instream_transform_fused(self):
        from repro.kernels.copy_engine import copy_2d, copy_2d_ref
        x = arr((64, 256))
        def t(v):
            return v * 3.0 + 1.0
        y = copy_2d(x, transform=t, backend="pallas", interpret=True)
        allclose(y, copy_2d_ref(x, t))

    def test_strided_nd(self):
        from repro.kernels.copy_engine import strided_copy_nd
        x = arr((3, 2, 64, 256))
        y = strided_copy_nd(x, backend="pallas", interpret=True)
        allclose(y, x)

    @pytest.mark.parametrize("shape", [(8, 128), (100, 300), (512, 1024)])
    def test_functional_reference_roundtrips(self, shape):
        """The plan's descriptor stream through `execute_batch` (gather to
        VMEM, scatter back) reproduces the array byte-exactly — the same
        descriptors the Pallas BlockSpecs walk."""
        from repro.kernels.copy_engine import copy_2d_reference
        x = np.asarray(arr(shape), np.float32)
        assert np.array_equal(copy_2d_reference(x), x)

    def test_functional_reference_matches_pallas(self):
        """Functional fabric == TPU fabric on the same plan."""
        from repro.kernels.copy_engine import copy_2d, copy_2d_reference
        x = arr((100, 300))
        y = copy_2d(x, backend="pallas", interpret=True)
        assert np.array_equal(np.asarray(y),
                              copy_2d_reference(np.asarray(x)))

    def test_functional_reference_instream_bytes(self):
        """An in-stream byte transform applies per burst on the inbound
        leg — invert twice is identity, invert once is not."""
        from repro.kernels.copy_engine import copy_2d_reference
        x = np.asarray(arr((64, 256)), np.float32)
        def inv(b):
            return 255 - b
        once = copy_2d_reference(x, instream=inv)
        assert not np.array_equal(once, x)
        twice = copy_2d_reference(once, instream=inv)
        assert np.array_equal(twice, x)


class TestInitEngine:
    @pytest.mark.parametrize("shape", [(8, 128), (100, 300), (256, 512)])
    def test_patterns(self, shape):
        from repro.kernels.init_engine import (iota_fill, iota_fill_ref,
                                               memset, memset_ref,
                                               prng_fill, prng_fill_ref)
        assert np.allclose(memset(shape, 2.5, backend="pallas",
                                  interpret=True), memset_ref(shape, 2.5))
        assert np.array_equal(
            iota_fill(shape, 3, backend="pallas", interpret=True),
            iota_fill_ref(shape, 3))
        assert np.array_equal(
            prng_fill(shape, 11, backend="pallas", interpret=True),
            prng_fill_ref(shape, 11))

    def test_prng_matches_rtl_byte_stream(self):
        """Kernel PRNG == Init pseudo-protocol byte stream (one oracle)."""
        from repro.core import InitPattern, init_stream
        from repro.kernels.init_engine import prng_fill
        words = prng_fill((8, 128), 42, jnp.uint32, backend="pallas",
                          interpret=True)
        rtl = init_stream(InitPattern.PSEUDORANDOM, 42, 0, 8 * 128 * 4)
        assert np.array_equal(
            np.asarray(words).reshape(-1).view(np.uint8), rtl)


class TestMatmul:
    # (256, 640, 256): the last 512-wide k block runs 384 past K, and
    # interpret mode fills what lies past the array with NaN
    @pytest.mark.parametrize("mkn", [(128, 128, 128), (200, 300, 150),
                                     (512, 1024, 256), (64, 2048, 64),
                                     (256, 640, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matmul(self, mkn, dtype):
        from repro.kernels.matmul_dma import matmul, matmul_ref
        M, K, N = mkn
        x, w = arr((M, K), dtype), arr((K, N), dtype)
        y = matmul(x, w, backend="pallas", interpret=True)
        allclose(y, matmul_ref(x, w), dtype)

    def test_epilogue(self):
        from repro.kernels.matmul_dma import matmul, matmul_ref
        x, w = arr((128, 256)), arr((256, 128))
        y = matmul(x, w, epilogue=jax.nn.relu, backend="pallas",
                   interpret=True)
        allclose(y, matmul_ref(x, w, epilogue=jax.nn.relu))


class TestFlashAttention:
    @pytest.mark.parametrize("case", [
        dict(B=2, Hq=4, Hkv=2, S=256, D=64, causal=True, window=0, cap=0.0),
        dict(B=1, Hq=4, Hkv=4, S=512, D=64, causal=True, window=128,
             cap=0.0),
        dict(B=1, Hq=2, Hkv=1, S=256, D=128, causal=True, window=0,
             cap=50.0),
        dict(B=1, Hq=2, Hkv=2, S=128, D=64, causal=False, window=0,
             cap=0.0),
        # S=200: the last 128-wide kv tile runs past the keys
        dict(B=1, Hq=4, Hkv=2, S=200, D=64, causal=True, window=0,
             cap=50.0),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, case, dtype):
        from repro.kernels.flash_attention import (attention_ref,
                                                   flash_attention)
        q = arr((case["B"], case["Hq"], case["S"], case["D"]), dtype, 0.5)
        k = arr((case["B"], case["Hkv"], case["S"], case["D"]), dtype, 0.5)
        v = arr((case["B"], case["Hkv"], case["S"], case["D"]), dtype, 0.5)
        out = flash_attention(q, k, v, causal=case["causal"],
                              window=case["window"], softcap=case["cap"],
                              block_q=128, block_k=128,
                              backend="pallas", interpret=True)
        ref = attention_ref(q, k, v, causal=case["causal"],
                            window=case["window"], softcap=case["cap"])
        allclose(out, ref, dtype)

    def test_chunked_flash_xla_path(self):
        """The XLA-path scan implementation == oracle (incl. SWA+softcap)."""
        pytest.importorskip(
            "repro.dist", reason="models.attention needs repro.dist")
        from repro.kernels.flash_attention.ref import attention_ref
        from repro.models.attention import chunked_flash
        q, k, v = (arr((2, 4, 300, 64), scale=0.5) for _ in range(3))
        out = chunked_flash(q, k, v, causal=True, window=100,
                            softcap_v=30.0, scale=0.125, chunk_q=128,
                            chunk_k=64)
        ref = attention_ref(q, k, v, causal=True, window=100, softcap=30.0,
                            scale=0.125)
        allclose(out, ref)


class TestDecodeAttention:
    @pytest.mark.parametrize("case", [
        dict(B=2, Hq=8, Hkv=2, S=512, D=64, kvlen=300, win=0),
        dict(B=1, Hq=4, Hkv=4, S=1024, D=128, kvlen=1024, win=0),
        dict(B=2, Hq=8, Hkv=4, S=2048, D=64, kvlen=1500, win=256),
        # S=300: the last 256-wide tile runs past the cache
        dict(B=2, Hq=8, Hkv=4, S=300, D=64, kvlen=300, win=0),
    ])
    def test_vs_ref(self, case):
        from repro.kernels.decode_attention import (decode_attention,
                                                    decode_attention_ref)
        q = arr((case["B"], case["Hq"], case["D"]), scale=0.5)
        k = arr((case["B"], case["Hkv"], case["S"], case["D"]), scale=0.5)
        v = arr((case["B"], case["Hkv"], case["S"], case["D"]), scale=0.5)
        out = decode_attention(q, k, v, kv_len=case["kvlen"],
                               window=case["win"], block_k=256,
                               backend="pallas", interpret=True)
        ref = decode_attention_ref(q, k, v, kv_len=case["kvlen"],
                                   window=case["win"])
        allclose(out, ref)

    @pytest.mark.parametrize("win,cap", [(0, 30.0), (200, 30.0)],
                             ids=["global", "windowed"])
    def test_per_row_kv_len(self, win, cap):
        """kv_len (B,): each row attends to its own length, from 1 to the
        cache's capacity; a scalar is that length given to every row."""
        from repro.kernels.decode_attention import (decode_attention,
                                                    decode_attention_ref)
        S = 640
        q = arr((4, 8, 64), scale=0.5)
        k, v = arr((4, 2, S, 64), scale=0.5), arr((4, 2, S, 64), scale=0.5)
        kw = dict(window=win, softcap=cap, scale=0.125)
        lens = jnp.asarray([1, 300, S, 77], jnp.int32)
        out = decode_attention(q, k, v, kv_len=lens, block_k=256,
                               backend="pallas", interpret=True, **kw)
        allclose(out, decode_attention_ref(q, k, v, kv_len=lens, **kw))
        for b in range(4):
            allclose(out[b:b + 1], decode_attention_ref(
                q[b:b + 1], k[b:b + 1], v[b:b + 1], kv_len=int(lens[b]),
                **kw))
        for backend in ("pallas", "xla"):
            one = decode_attention(q, k, v, kv_len=300, block_k=256,
                                   backend=backend, interpret=True, **kw)
            every = decode_attention(q, k, v, kv_len=jnp.full((4,), 300,
                                                              jnp.int32),
                                     block_k=256, backend=backend,
                                     interpret=True, **kw)
            assert np.array_equal(np.asarray(one), np.asarray(every))

    def test_dynamic_kv_len(self):
        """kv_len may be a traced scalar (decode loops)."""
        from repro.kernels.decode_attention import (decode_attention,
                                                    decode_attention_ref)
        q, k, v = arr((1, 4, 64)), arr((1, 2, 256, 64)), arr((1, 2, 256, 64))
        for kvlen in (17, 100, 256):
            out = decode_attention(q, k, v, kv_len=jnp.int32(kvlen),
                                   block_k=128, backend="pallas",
                                   interpret=True)
            ref = decode_attention_ref(q, k, v, kv_len=kvlen)
            allclose(out, ref)


class TestSSD:
    @pytest.mark.parametrize("case", [
        dict(B=2, H=4, G=2, S=256, P=32, N=64, chunk=64),
        dict(B=1, H=8, G=1, S=128, P=64, N=32, chunk=32),
    ])
    def test_vs_sequential_scan(self, case):
        from repro.kernels.ssd import ssd, ssd_chunked_ref, ssd_ref
        B, H, G, S, P, N = (case[k] for k in "BHGSPN")
        x = arr((B, H, S, P))
        dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, H, S)), jnp.float32)
        A = jnp.asarray(-RNG.uniform(0.5, 2.0, H), jnp.float32)
        D = arr((H,))
        Bm = arr((B, G, S, N), scale=0.3)
        Cm = arr((B, G, S, N), scale=0.3)
        ref = ssd_ref(x, dt, A, D, Bm, Cm)
        out = ssd(x, dt, A, D, Bm, Cm, chunk=case["chunk"],
                  backend="pallas", interpret=True)
        chk = ssd_chunked_ref(x, dt, A, D, Bm, Cm, chunk=case["chunk"])
        allclose(out, ref)
        allclose(chk, ref)

    def test_final_state_matches_continuation(self):
        """Prefill state + decode step == longer prefill (handoff exact)."""
        from repro.kernels.ssd import ssd, ssd_ref
        B, H, G, S, P, N = 1, 2, 1, 64, 16, 32
        x = arr((B, H, S, P))
        dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, H, S)), jnp.float32)
        A = jnp.asarray(-RNG.uniform(0.5, 2.0, H), jnp.float32)
        D = arr((H,))
        Bm, Cm = arr((B, G, S, N), scale=0.3), arr((B, G, S, N), scale=0.3)
        y, state = ssd(x, dt, A, D, Bm, Cm, chunk=32, return_state=True,
                       backend="xla")
        # recompute state with the sequential recurrence
        hpg = H // G
        h = np.zeros((B, H, N, P), np.float32)
        for t in range(S):
            for b in range(B):
                for hh in range(H):
                    g = hh // hpg
                    a = np.exp(float(A[hh]) * float(dt[b, hh, t]))
                    h[b, hh] = a * h[b, hh] + float(dt[b, hh, t]) * \
                        np.outer(np.asarray(Bm[b, g, t]),
                                 np.asarray(x[b, hh, t]))
        np.testing.assert_allclose(np.asarray(state), h, rtol=2e-4,
                                   atol=2e-5)
