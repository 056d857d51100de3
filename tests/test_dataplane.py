"""Vectorized functional data plane: `execute_batch` vs the scalar oracle.

The grouped gather/scatter back-end must be byte-identical to running the
scalar `execute` over the same legalized bursts — across every protocol
pair, all three Init patterns, in-stream accelerators, nonzero stream
bases, and every error-handler verb.  The scalar path stays in the tree
exactly so these tests have an oracle.

Also covers the back-end bugfixes that ride along:
* `MemoryMap.read`/`write` reject negative addresses (slice wrap-around),
* `stream_base` actually applies to generator fetches,
* `TransferError.index` names the offender exactly (duplicate bursts).
"""

import dataclasses
import random

import numpy as np
import pytest

from repro.core import backend
from repro.core import (BackendOptions, DescriptorBatch, ErrorPolicy,
                        IDMAEngine, InitPattern, MemoryMap, Protocol,
                        Transfer1D, TransferError, check_legal,
                        check_legal_batch, execute, execute_batch,
                        init_stream, legalize, legalize_batch)

MEM_PROTOS = [Protocol.AXI4, Protocol.AXI_LITE, Protocol.AXI_STREAM,
              Protocol.OBI, Protocol.TILELINK, Protocol.HBM, Protocol.VMEM]
SPACE = 1 << 16
PATTERNS = list(InitPattern)


def make_mem(seed=0):
    mem = MemoryMap.create({p: SPACE for p in MEM_PROTOS})
    rng = np.random.default_rng(seed)
    for p in MEM_PROTOS:
        mem.spaces[p][:] = rng.integers(0, 256, SPACE, dtype=np.uint8)
    return mem


def rand_legal_batch(rng, n_transfers):
    """Random legalized stream over all protocol pairs and Init patterns.

    Sources read from the lower half of each space, destinations are
    bump-allocated from the upper half, so no burst reads bytes another
    burst writes (the documented no-RAW contract of `execute_batch`).
    """
    ts = []
    cursor = {p: SPACE // 2 for p in MEM_PROTOS}
    for i in range(n_transfers):
        sp = rng.choice(MEM_PROTOS + [Protocol.INIT])
        dp = rng.choice(MEM_PROTOS)
        length = rng.choice([0, 1, 3, 17, 255, 1000, rng.randrange(2000)])
        if cursor[dp] + length > SPACE:
            continue
        dst = cursor[dp]
        cursor[dp] += length
        src = rng.randrange(0, SPACE // 2 - length) \
            if sp is not Protocol.INIT else rng.randrange(0, 5000)
        opts = BackendOptions(
            max_burst=rng.choice([0, 0, 7, 64, 1000]),
            reduce_len=rng.choice([0, 0, 33]),
            init_pattern=rng.choice(PATTERNS),
            init_value=rng.randrange(0, 1000))
        ts.append(Transfer1D(src, dst, length, sp, dp, options=opts,
                             transfer_id=i))
    return legalize_batch(DescriptorBatch.from_transfers(ts), bus_width=8)


def assert_spaces_equal(m1, m2, ctx=""):
    for p in MEM_PROTOS:
        assert np.array_equal(m1.spaces[p], m2.spaces[p]), f"{ctx}: {p}"


class TestExecuteBatchOracle:
    def test_randomized_all_protocol_pairs(self):
        """Acceptance: byte-identical to scalar `execute` on randomized
        legalized batches (all protocol pairs, all Init patterns)."""
        rng = random.Random(11)
        for trial in range(30):
            legal = rand_legal_batch(rng, rng.randrange(1, 14))
            m1, m2 = make_mem(trial), make_mem(trial)
            a = execute(legal.to_transfers(), m1, bus_width=8)
            b = execute_batch(legal, m2, bus_width=8)
            assert a == b, f"trial {trial}"
            assert_spaces_equal(m1, m2, f"trial {trial}")

    def test_every_pair_and_pattern_systematically(self):
        """One page-straddling transfer per (src, dst) pair, one per Init
        pattern — no pair rides only on random coverage."""
        srcs = [(p, None) for p in MEM_PROTOS] + \
            [(Protocol.INIT, pat) for pat in PATTERNS]
        for sp, pat in srcs:
            for dp in MEM_PROTOS:
                opts = BackendOptions() if pat is None else BackendOptions(
                    init_pattern=pat, init_value=0x5A)
                t = Transfer1D(4096 - 3, SPACE // 2 + 4096 - 9, 5000,
                               sp, dp, options=opts)
                legal = legalize_batch(
                    DescriptorBatch.from_transfers([t]), bus_width=8)
                m1, m2 = make_mem(7), make_mem(7)
                execute(legal.to_transfers(), m1, bus_width=8)
                execute_batch(legal, m2, bus_width=8)
                assert_spaces_equal(m1, m2, f"{sp}->{dp} {pat}")

    def test_instream_applied_per_chunk(self):
        """The in-stream accelerator runs per burst chunk on both paths."""
        sizes1, sizes2 = [], []

        def xform(track):
            def f(d):
                track.append(d.shape[0])
                return 255 - d
            return f

        rng = random.Random(3)
        legal = rand_legal_batch(rng, 10)
        m1, m2 = make_mem(1), make_mem(1)
        execute(legal.to_transfers(), m1, bus_width=8,
                instream=xform(sizes1))
        execute_batch(legal, m2, bus_width=8, instream=xform(sizes2))
        assert_spaces_equal(m1, m2)
        assert sorted(sizes1) == sorted(sizes2)   # same chunking granularity

    def test_empty_batch(self):
        assert execute_batch(DescriptorBatch.empty(), make_mem()) == 0


# (src space, dst space, src addrs, dst addrs, lengths) of one copy group,
# on spaces of ROWS_SPACE bytes: each case moves its bursts as rows.
ROWS_SPACE = 1 << 21
_PAGE, _ROW = 16 * 512, 512       # chatglm3-6b KV page and token row
_H, _V = Protocol.HBM, Protocol.VMEM
ROW_COPY_CASES = {
    # a decode gather: 131 whole pages from scattered pool pages
    "page_gather_8192": (_H, _V, np.random.default_rng(1).permutation(
        ROWS_SPACE // _PAGE)[:131] * _PAGE,
        np.arange(131) * _PAGE, [_PAGE] * 131),
    # a prefill chunk's staging appends: 512 rows into their page slots
    "row_append_512": (_V, _H, np.arange(512) * _ROW,
                       np.random.default_rng(2).permutation(
                           ROWS_SPACE // _ROW)[:512] * _ROW, [_ROW] * 512),
    "burst_8": (_H, _V, np.arange(300) * 24 + 5, np.arange(300) * 16,
                [8] * 300),
    "mixed_lengths": (_H, _V, [0, 9000, 20000, 30001, 40000, 50000],
                      [100000, 0, 60000, 70000, 80000, 90000],
                      [8192, 512, 8, 3, 8192, 512]),
    "unaligned": (_H, _V, [4099, 12345, 777], [77, 3001, 9999],
                  [513, 513, 513]),
    "ends_on_last_byte": (_H, _V, [ROWS_SPACE - _PAGE, 0],
                          [ROWS_SPACE - _PAGE, ROWS_SPACE - 2 * _PAGE],
                          [_PAGE, _PAGE]),
    # one space, each burst's source and destination ranges overlapping
    "same_space_overlap": (_H, _H, [1000, 20000, 40000],
                           [1300, 19700, 40001], [512, 512, 8192]),
    "same_destination": (_H, _V, [0, 9000, 30000], [5000, 5000, 5000],
                         [512, 512, 512]),
}


def _rows_mem(seed):
    mem = MemoryMap.create({_H: ROWS_SPACE, _V: ROWS_SPACE})
    rng = np.random.default_rng(seed)
    for p in (_H, _V):
        mem.spaces[p][:] = rng.integers(0, 256, ROWS_SPACE, dtype=np.uint8)
    return mem


def _rows_batch(sp, dp, sa, da, lens):
    return DescriptorBatch.from_arrays(
        src_addr=np.asarray(sa), dst_addr=np.asarray(da),
        length=np.asarray(lens), src_protocol=sp, dst_protocol=dp)


class TestRowCopy:
    """Each burst moves as one row of a strided window over its space;
    the scalar oracle moves it byte range by byte range."""

    @pytest.mark.parametrize("case", sorted(ROW_COPY_CASES))
    def test_matches_scalar_oracle(self, case):
        batch = _rows_batch(*ROW_COPY_CASES[case])
        m1, m2 = _rows_mem(5), _rows_mem(5)
        a = execute(batch.to_transfers(), m1, bus_width=8)
        b = execute_batch(batch, m2, bus_width=8)
        assert a == b == int(batch.length.sum())
        for p in (_H, _V):
            assert np.array_equal(m1.spaces[p], m2.spaces[p]), p

    def test_reads_every_source_before_writing(self):
        """Burst 0 writes what burst 1 reads: the batch path moves burst
        1's bytes as they were before the call (decoupled read/write)."""
        batch = _rows_batch(_H, _H, [0, 4096], [4096, 8192], [512, 512])
        mem = _rows_mem(6)
        before = mem.spaces[_H].copy()
        execute_batch(batch, mem, bus_width=8)
        hbm = mem.spaces[_H]
        assert np.array_equal(hbm[4096:4608], before[:512])
        assert np.array_equal(hbm[8192:8704], before[4096:4608])

    def test_rows_beyond_one_chunk_copied_whole(self, monkeypatch):
        """A bin larger than one `EXEC_CHUNK_BYTES` slice moves in several
        slices, every row of every slice."""
        monkeypatch.setattr(backend, "EXEC_CHUNK_BYTES", 3 * _ROW)
        sa = np.random.default_rng(3).permutation(64)[:40] * _PAGE
        batch = _rows_batch(_H, _V, np.concatenate([sa, sa + 7]),
                            np.arange(80) * _ROW + 11,
                            [_ROW] * 40 + [8] * 40)
        m1, m2 = _rows_mem(7), _rows_mem(7)
        execute(batch.to_transfers(), m1, bus_width=8)
        execute_batch(batch, m2, bus_width=8)
        for p in (_H, _V):
            assert np.array_equal(m1.spaces[p], m2.spaces[p]), p


class TestStreamBase:
    OPTS = BackendOptions(init_pattern=InitPattern.PSEUDORANDOM,
                          init_value=7)

    def test_nonzero_base_applies_to_generator_fetch(self):
        """Regression: the per-transfer-id origin was computed but never
        applied — a nonzero `stream_base` must shift the Init stream."""
        t = Transfer1D(100, 0, 256, Protocol.INIT, Protocol.OBI,
                       options=self.OPTS, transfer_id=3)
        bursts = legalize(t, bus_width=8)
        mem = make_mem()
        execute(bursts, mem, bus_width=8, stream_base={3: 100})
        want = init_stream(InitPattern.PSEUDORANDOM, 7, 0, 256)
        assert np.array_equal(mem.spaces[Protocol.OBI][:256], want)

    def test_default_base_is_absolute_offset(self):
        """Docstring contract: without `stream_base` the stream offset is
        the absolute source address, so any split reproduces the unsplit
        stream."""
        t = Transfer1D(100, 0, 256, Protocol.INIT, Protocol.OBI,
                       options=self.OPTS)
        mem = make_mem()
        execute(legalize(t, bus_width=8), mem, bus_width=8)
        want = init_stream(InitPattern.PSEUDORANDOM, 7, 100, 256)
        assert np.array_equal(mem.spaces[Protocol.OBI][:256], want)

    def test_split_across_calls_same_stream(self):
        """A legalized Init transfer split across separate execute calls
        (distinct back-end ports, replays) produces the unsplit stream."""
        t = Transfer1D(64, 0, 1024, Protocol.INIT, Protocol.OBI,
                       options=self.OPTS, transfer_id=9)
        bursts = legalize(dataclasses.replace(
            t, options=dataclasses.replace(self.OPTS, max_burst=96)),
            bus_width=8)
        assert len(bursts) > 2
        mem = make_mem()
        base = {9: 64}
        for b in bursts:            # one call per burst: worst-case split
            execute([b], mem, bus_width=8, stream_base=base)
        want = init_stream(InitPattern.PSEUDORANDOM, 7, 0, 1024)
        assert np.array_equal(mem.spaces[Protocol.OBI][:1024], want)

    def test_batch_matches_scalar_with_base(self):
        t = Transfer1D(40, 0, 512, Protocol.INIT, Protocol.OBI,
                       options=self.OPTS, transfer_id=5)
        legal = legalize_batch(DescriptorBatch.from_transfers([t]), 8)
        m1, m2 = make_mem(), make_mem()
        execute(legal.to_transfers(), m1, bus_width=8, stream_base={5: 24})
        execute_batch(legal, m2, bus_width=8, stream_base={5: 24})
        assert_spaces_equal(m1, m2)


class TestMemoryMapBounds:
    def test_negative_read_rejected(self):
        """Regression: a negative address passed the end-of-buffer guard
        and silently wrapped via Python slice semantics."""
        mem = make_mem()
        with pytest.raises(IndexError, match="negative"):
            mem.read(Protocol.AXI4, -4, 4)

    def test_negative_write_rejected(self):
        mem = make_mem()
        before = mem.spaces[Protocol.AXI4].copy()
        with pytest.raises(IndexError, match="negative"):
            mem.write(Protocol.AXI4, -8, np.zeros(4, dtype=np.uint8))
        assert np.array_equal(mem.spaces[Protocol.AXI4], before)

    def test_negative_row_in_batch_is_a_transfer_error(self):
        """execute_batch must not let fancy indexing wrap a negative row."""
        batch = DescriptorBatch.from_arrays(
            src_addr=np.array([0, -64]), dst_addr=np.array([0, 64]),
            length=np.array([64, 64]),
            src_protocol=Protocol.HBM, dst_protocol=Protocol.VMEM)
        mem = make_mem()
        before = mem.spaces[Protocol.VMEM].copy()
        with pytest.raises(TransferError) as ei:
            execute_batch(batch, mem, bus_width=8)
        assert ei.value.index == 1
        assert "negative" in ei.value.reason
        # row 0 executed, row 1 had no effect
        assert np.array_equal(mem.spaces[Protocol.VMEM][:64],
                              mem.spaces[Protocol.HBM][:64])
        assert np.array_equal(mem.spaces[Protocol.VMEM][64:], before[64:])


class TestTransferErrorIndex:
    def test_injected_fault_reports_index(self):
        legal = rand_legal_batch(random.Random(5), 8)
        k = len(legal) // 2
        m1, m2 = make_mem(), make_mem()
        with pytest.raises(TransferError) as e1:
            execute(legal.to_transfers(), m1, bus_width=8, fail_at=k)
        with pytest.raises(TransferError) as e2:
            execute_batch(legal, m2, bus_width=8, fail_at=k)
        assert e1.value.index == e2.value.index == k
        assert_spaces_equal(m1, m2, "partial state at fault")

    def test_duplicate_bursts_get_exact_index(self):
        """Identical rows are indistinguishable by value — the index must
        still name the actual offender."""
        row = dict(src_addr=np.array([0, 128, 0]),
                   dst_addr=np.array([0, 128, 0]),
                   length=np.array([64, 64, 64]))
        batch = DescriptorBatch.from_arrays(
            src_protocol=Protocol.HBM, dst_protocol=Protocol.VMEM, **row)
        with pytest.raises(TransferError) as ei:
            execute_batch(batch, make_mem(), bus_width=8, fail_at=2)
        assert ei.value.index == 2

    def test_out_of_bounds_burst_reports_index_and_partial_state(self):
        batch = DescriptorBatch.from_arrays(
            src_addr=np.array([0, SPACE + 64, 128]),
            dst_addr=np.array([0, 64, 128]),
            length=np.array([64, 64, 64]),
            src_protocol=Protocol.HBM, dst_protocol=Protocol.VMEM)
        mem = make_mem()
        with pytest.raises(TransferError) as ei:
            execute_batch(batch, mem, bus_width=8)
        assert ei.value.index == 1
        assert "beyond" in ei.value.reason
        assert np.array_equal(mem.spaces[Protocol.VMEM][:64],
                              mem.spaces[Protocol.HBM][:64])


def scalar_run_oracle(eng, transfer, fail_at, stats):
    """The engine's error-policy loop expressed over the scalar back-end
    (`execute` + object burst lists) — the oracle for `_run`."""
    ports = eng.lower(transfer)
    fail_pending = fail_at
    for bursts in ports:
        done = 0
        replays = 0
        while done < len(bursts):
            fail = None
            if fail_pending is not None and \
                    done <= fail_pending < len(bursts):
                fail = fail_pending - done
            try:
                stats["bytes"] += execute(
                    bursts[done:], eng.mem, bus_width=eng.bus_width,
                    fail_at=fail)
                done = len(bursts)
            except TransferError as err:
                idx = done + err.index
                stats["errors"] += 1
                stats["bytes"] += sum(b.length for b in bursts[done:idx])
                action = eng.error_policy.action
                if action == "abort":
                    raise
                if action == "continue":
                    fail_pending = None
                    done = idx + 1
                    continue
                replays += 1
                stats["replays"] += 1
                if replays > eng.error_policy.max_replays:
                    raise
                fail_pending = None
                done = idx


class TestEnginePolicyMatrix:
    """Satellite: abort/continue/replay x fault at first/middle/last burst
    x multi-back-end port split — byte-identical to the scalar oracle."""

    @staticmethod
    def build(action, backends):
        kw = dict(num_backends=backends, backend_boundary=512) \
            if backends > 1 else {}
        mem = MemoryMap.create({Protocol.AXI4: 1 << 14,
                                Protocol.OBI: 1 << 14})
        rng = np.random.default_rng(5)
        mem.spaces[Protocol.AXI4][:] = rng.integers(
            0, 256, 1 << 14, dtype=np.uint8)
        return IDMAEngine(mem=mem,
                          error_policy=ErrorPolicy(action=action), **kw), mem

    @pytest.mark.parametrize("action", ["abort", "continue", "replay"])
    @pytest.mark.parametrize("pos", ["first", "middle", "last"])
    @pytest.mark.parametrize("backends", [1, 2])
    def test_policy_fault_position_backends(self, action, pos, backends):
        t = Transfer1D(0, 0, 4096, Protocol.AXI4, Protocol.OBI)
        probe, _ = self.build(action, backends)
        n0 = len(probe.lower(t)[0])
        assert n0 >= 3
        fail = {"first": 0, "middle": n0 // 2, "last": n0 - 1}[pos]

        eng, mem = self.build(action, backends)
        eng.inject_fault(fail)
        raised = None
        try:
            eng.submit(t)
        except TransferError as err:
            raised = err

        oracle, mem2 = self.build(action, backends)
        stats = {"bytes": 0, "errors": 0, "replays": 0}
        oracle_raised = None
        try:
            scalar_run_oracle(oracle, dataclasses.replace(t, transfer_id=1),
                              fail, stats)
        except TransferError as err:
            oracle_raised = err

        assert (raised is None) == (oracle_raised is None)
        for p in (Protocol.AXI4, Protocol.OBI):
            assert np.array_equal(mem.spaces[p], mem2.spaces[p]), \
                f"{action}/{pos}/{backends}: {p}"
        assert eng.stats.bytes_moved == stats["bytes"]
        assert eng.stats.errors == stats["errors"]
        assert eng.stats.replays == stats["replays"]

    def test_replay_through_batch_payload(self):
        """dispatch_batch traffic heals through the replay verb too."""
        eng, mem = self.build("replay", 1)
        batch = DescriptorBatch.from_arrays(
            src_addr=np.arange(4, dtype=np.int64) * 256,
            dst_addr=np.arange(4, dtype=np.int64) * 256,
            length=np.full(4, 256, dtype=np.int64),
            src_protocol=Protocol.AXI4, dst_protocol=Protocol.OBI)
        eng.inject_fault(2)
        eng.dispatch_batch(batch)
        eng.wait_all()
        assert eng.stats.replays == 1 and eng.stats.errors == 1
        assert np.array_equal(mem.spaces[Protocol.OBI][:1024],
                              mem.spaces[Protocol.AXI4][:1024])


class TestInitSplitInvariance:
    def test_same_stream_across_backend_split(self):
        """An Init transfer mp_dist'ed over 4 back-end ports writes the
        same bytes as the single-port engine."""
        opts = BackendOptions(init_pattern=InitPattern.PSEUDORANDOM,
                              init_value=5)
        results = []
        for nb in (1, 4):
            kw = dict(num_backends=nb, backend_boundary=256) \
                if nb > 1 else {}
            mem = MemoryMap.create({Protocol.OBI: 1 << 13})
            eng = IDMAEngine(mem=mem, **kw)
            eng.submit(Transfer1D(0, 0, 4096, Protocol.INIT, Protocol.OBI,
                                  options=opts))
            results.append(mem.spaces[Protocol.OBI][:4096].copy())
        want = init_stream(InitPattern.PSEUDORANDOM, 5, 0, 4096)
        assert np.array_equal(results[0], want)
        assert np.array_equal(results[1], want)


class TestCheckLegalBatch:
    def rand_raw(self, rng, n):
        ts = []
        for i in range(n):
            sp = rng.choice(MEM_PROTOS + [Protocol.INIT])
            dp = rng.choice(MEM_PROTOS)
            ts.append(Transfer1D(
                rng.randrange(0, 1 << 30), rng.randrange(0, 1 << 30),
                rng.choice([1, 3, 64, 255, 4096, 10000]), sp, dp,
                transfer_id=i))
        return ts

    def test_matches_scalar_raise_and_message(self):
        rng = random.Random(21)
        raised = 0
        for trial in range(80):
            ts = self.rand_raw(rng, rng.randrange(1, 10))
            batch = DescriptorBatch.from_transfers(ts)
            err_obj = err_bat = None
            try:
                check_legal(ts, 8)
            except ValueError as e:
                err_obj = str(e)
            try:
                check_legal_batch(batch, 8)
            except ValueError as e:
                err_bat = str(e)
            assert (err_obj is None) == (err_bat is None), f"trial {trial}"
            if err_obj is not None:
                assert err_obj == err_bat, f"trial {trial}"
                raised += 1
        assert raised > 10       # the sweep actually exercised violations

    def test_legalized_output_passes(self):
        legal = rand_legal_batch(random.Random(2), 12)
        check_legal_batch(legal, 8)


class TestNoObjectMaterialization:
    def test_run_path_never_calls_to_transfers(self, monkeypatch):
        """Acceptance: the functional hot path stays on arrays end-to-end
        — submit and dispatch_batch work with to_transfers() poisoned."""
        mem = MemoryMap.create({Protocol.AXI4: 1 << 13, Protocol.OBI: 1 << 13})
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, 4096, dtype=np.uint8)
        mem.spaces[Protocol.AXI4][:4096] = data
        eng = IDMAEngine(mem=mem, num_backends=2, backend_boundary=512,
                         num_channels=2)

        def boom(self):
            raise AssertionError("to_transfers() on the data plane")

        monkeypatch.setattr(DescriptorBatch, "to_transfers", boom)
        eng.submit(Transfer1D(0, 0, 2048, Protocol.AXI4, Protocol.OBI))
        batch = DescriptorBatch.from_arrays(
            src_addr=np.array([2048, 3072]), dst_addr=np.array([2048, 3072]),
            length=np.array([1024, 1024]),
            src_protocol=Protocol.AXI4, dst_protocol=Protocol.OBI)
        eng.dispatch_batch(batch)
        eng.wait_all()
        assert np.array_equal(mem.spaces[Protocol.OBI][:4096], data[:4096])
