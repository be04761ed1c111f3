"""Kernel correctness: the TPU sweep tiers vs the hashlib oracle (B5/B6).

The correctness contract (reference ``bitcoin/hash.go:13-17``): for every
nonce, ``Hash = BigEndian.Uint64(SHA256(b"<data> <nonce-decimal>")[:8])``,
and a range sweep returns the lexicographic min with lowest-nonce ties.
Ranges here deliberately cross decimal-digit-count boundaries — the hashed
string's length changes there, which is the hard part of the kernel layout
(SURVEY §7 hard part 3).

Test shapes stay small (low ``max_k``, short ranges): every distinct
(layout, k, batch) class is a fresh XLA:CPU compile, and Pallas-interpret
executes tiles in Python — big shapes belong on real TPU via bench.py.
"""

import hashlib

import pytest

from bitcoin_miner_tpu.bitcoin.hash import hash_nonce, min_hash_range
from bitcoin_miner_tpu.ops.sha256 import build_layout, digest_u64_py
from bitcoin_miner_tpu.ops.sweep import decompose_range, sweep_min_hash


class TestLayout:
    @pytest.mark.parametrize("data", [b"", b"x", b"cmu440", b"a" * 55, b"b" * 200])
    @pytest.mark.parametrize("digits", ["7", "42", "999", "18446744073709551615"])
    def test_layout_matches_hashlib(self, data, digits):
        layout = build_layout(data, len(digits))
        expect = int.from_bytes(
            hashlib.sha256(data + b" " + digits.encode()).digest()[:8], "big"
        )
        assert digest_u64_py(layout, digits) == expect

    def test_long_data_folds_midstate(self):
        # data >= 64 bytes: at least one whole block folds host-side
        layout = build_layout(b"q" * 130, 3)
        assert layout.n_tail_blocks < (130 + 1 + 3 + 9 + 63) // 64


class TestCompress:
    def test_unrolled_compress_matches_hashlib(self):
        """Direct check of the Mosaic-path compression (scalar shapes compile
        fast even on XLA:CPU) — the only CPU coverage of the unrolled form,
        which otherwise runs exclusively on real TPU."""
        import jax.numpy as jnp

        from bitcoin_miner_tpu.ops.sha256 import H0, compress

        msg = bytearray(64)
        msg[:3] = b"abc"
        msg[3] = 0x80
        msg[-8:] = (24).to_bytes(8, "big")
        w = [jnp.uint32(int.from_bytes(msg[i : i + 4], "big")) for i in range(0, 64, 4)]
        out = compress(tuple(jnp.uint32(int(x)) for x in H0), w)
        digest = b"".join(int(x).to_bytes(4, "big") for x in out)
        assert digest == hashlib.sha256(b"abc").digest()

    @pytest.mark.parametrize("p", [0, 3, 7, 16])
    def test_group_state_split_is_bit_identical(self, p):
        """ISSUE 14 contract: compress(stop_round=p) -> compress(
        group_state=) composes to the whole compression bit-exactly, for
        both round forms and CROSS-form (the factored xla tier produces
        the prefix and resumes with the same rolled fn; the pallas
        interpret path mixes via its comp shim)."""
        import jax.numpy as jnp

        from bitcoin_miner_tpu.ops.sha256 import H0, compress, compress_rolled

        msg = bytearray(64)
        msg[:3] = b"abc"
        msg[3] = 0x80
        msg[-8:] = (24).to_bytes(8, "big")
        w = [
            jnp.uint32(int.from_bytes(msg[i : i + 4], "big"))
            for i in range(0, 64, 4)
        ]
        st = tuple(jnp.uint32(int(x)) for x in H0)
        ref = [int(x) for x in compress(st, w)]
        for producer in (compress, compress_rolled):
            # Prefix consumes only w[0:p] — the factored kernels hand the
            # producer group-scalar words; the resume gets the full 16.
            gs = producer(st, w[:p], stop_round=p)
            assert gs[0] == p
            for resumer in (compress, compress_rolled):
                out = [int(x) for x in resumer(st, w, group_state=gs)]
                assert out == ref, (producer.__name__, resumer.__name__)
        # final_only output masks compose with the resume too.
        gs = compress(st, w, stop_round=p)
        fo = compress(st, w, group_state=gs, final_only=True)
        assert [int(fo[0]), int(fo[1])] == ref[:2]
        (h0,) = compress(st, w, group_state=gs, final_only="h0")
        assert int(h0) == ref[0]

    def test_stop_round_past_schedule_rejected(self):
        import jax.numpy as jnp

        from bitcoin_miner_tpu.ops.sha256 import H0, compress, compress_rolled

        w = [jnp.uint32(0)] * 16
        st = tuple(jnp.uint32(int(x)) for x in H0)
        for fn in (compress, compress_rolled):
            with pytest.raises(ValueError):
                fn(st, w, stop_round=17)


class TestFactorSplit:
    """The outer/inner digit split + per-group patch table (ISSUE 14)."""

    def test_split_positions_and_first_inner_word(self):
        layout = build_layout(b"cmu440", 10)
        sp = layout.factor(6, 3)
        assert (sp.k_out, sp.k_in) == (3, 3)
        low = layout.digit_pos[4:]
        assert sp.outer_pos == tuple(low[:3])
        assert sp.inner_pos == tuple(low[3:])
        assert sp.first_inner_word == min(dp.word for dp in sp.inner_pos)

    def test_invalid_k_in_rejected(self):
        from bitcoin_miner_tpu.ops.sha256 import factor_low_pos

        layout = build_layout(b"cmu440", 10)
        low = layout.digit_pos[4:]
        for bad in (0, 6, 7):
            with pytest.raises(ValueError):
                factor_low_pos(low, bad)

    def test_outer_patch_table_matches_ascii(self):
        from bitcoin_miner_tpu.ops.sha256 import outer_patch_table

        layout = build_layout(b"cmu440", 10)
        sp = layout.factor(6, 3)
        words, table = outer_patch_table(sp.outer_pos)
        assert table.shape == (1000, len(words))
        for g in (0, 7, 427, 999):
            expect = {}
            for j, dp in enumerate(sp.outer_pos):
                digit = f"{g:03d}"[j]
                expect[dp.word] = expect.get(dp.word, 0) | (
                    ord(digit) << dp.shift
                )
            assert [int(x) for x in table[g]] == [expect[w] for w in words]


class TestDecompose:
    def test_cover_exact_no_overlap(self):
        lower, upper = 7, 123456
        seen = []
        for g in decompose_range(lower, upper, max_k=3):
            for c in g.chunks:
                seen.extend(range(c.base + c.lo_off, c.base + c.hi_off))
        assert seen == list(range(lower, upper + 1))

    def test_single_nonce(self):
        groups = list(decompose_range(5, 5))
        assert len(groups) == 1
        (c,) = groups[0].chunks
        assert (c.base + c.lo_off, c.base + c.hi_off) == (5, 6)

    def test_empty_range_raises(self):
        with pytest.raises(ValueError):
            list(decompose_range(10, 9))


class TestXlaTier:
    @pytest.mark.parametrize(
        "data,lo,hi",
        [
            ("cmu440", 0, 1205),       # crosses 1->2->3->4 digit boundaries
            ("x", 95, 1205),           # partial buckets on both ends
            ("", 0, 150),              # empty job data
            ("padding-edge-55bytes-" + "z" * 33, 1, 99),  # 2-block tail
        ],
    )
    def test_matches_oracle(self, data, lo, hi):
        r = sweep_min_hash(data, lo, hi, backend="xla", max_k=2)
        assert (r.hash, r.nonce) == min_hash_range(data, lo, hi)
        assert r.lanes_swept == hi - lo + 1

    def test_single_nonce_range(self):
        r = sweep_min_hash("solo", 12345, 12345, backend="xla", max_k=2)
        assert (r.hash, r.nonce) == (hash_nonce("solo", 12345), 12345)

    def test_20_digit_nonces(self):
        # uint64-max territory: 2^64-1 has 20 digits (bitcoin/message.go:21)
        top = (1 << 64) - 1
        r = sweep_min_hash("big", top - 50, top, backend="xla", max_k=1)
        assert (r.hash, r.nonce) == min_hash_range("big", top - 50, top)


class TestPallasTier:
    """Pallas kernel in interpreter mode (Mosaic needs real TPU hardware);
    bit-exactness of the same kernel compiled for TPU is rechecked by
    bench.py on the real chip."""

    def test_matches_oracle_small(self):
        r = sweep_min_hash(
            "abc", 95, 321, backend="pallas", interpret=True, batch=2, max_k=2
        )
        assert (r.hash, r.nonce) == min_hash_range("abc", 95, 321)

    def test_matches_xla_tier_across_boundary(self):
        data, lo, hi = "cmu440", 985, 1040
        rp = sweep_min_hash(
            data, lo, hi, backend="pallas", interpret=True, batch=2, max_k=2
        )
        rx = sweep_min_hash(data, lo, hi, backend="xla", max_k=2)
        assert (rp.hash, rp.nonce) == (rx.hash, rx.nonce)

    def test_non_default_tile(self):
        # The autotune path plumbs tile through sweep_min_hash; a clamped
        # non-default tile must stay bit-exact.
        r = sweep_min_hash(
            "abc", 95, 321, backend="pallas", interpret=True,
            batch=2, max_k=2, tile=2048,
        )
        assert (r.hash, r.nonce) == min_hash_range("abc", 95, 321)

    def test_group_fold_multiple_chunks_per_program(self):
        # batch=4 with cpb=2: two chunk rows fold inside each grid program
        # (the per-group running-min path), and a range that doesn't fill
        # all rows leaves a MIXED group whose padding row must mask out.
        r = sweep_min_hash(
            "abc", 95, 321, backend="pallas", interpret=True,
            batch=4, cpb=2, max_k=2,
        )
        assert (r.hash, r.nonce) == min_hash_range("abc", 95, 321)

    def test_group_fold_tie_breaks_to_lowest_nonce(self):
        # Duplicate rows covering the same range tie on (h0, h1) in the
        # SAME program's group fold; the winner must be the lower row.
        from bitcoin_miner_tpu.ops.pallas_sha256 import make_pallas_minhash
        import numpy as np

        layout = build_layout(b"tie", 3)
        fn = make_pallas_minhash(
            layout.n_tail_blocks, layout.digit_pos[1:], 2,
            batch=2, cpb=2, interpret=True,
        )
        midstate = np.array(layout.midstate, dtype=np.uint32)
        row = np.array(layout.tail_template, dtype=np.uint64)
        dp = layout.digit_pos[0]
        row[dp.word] |= np.uint64(ord("1") << dp.shift)
        tailcb = np.tile(
            np.concatenate([row, [0, 100]]).astype(np.uint32), (2, 1)
        )
        _h0, _h1, idx = fn(midstate, tailcb)
        assert int(idx) < 100  # row 0, not the duplicate row 1

    def test_non_divisor_cpb_rejected(self):
        with pytest.raises(ValueError, match="cpb"):
            sweep_min_hash(
                "abc", 95, 99, backend="pallas", interpret=True,
                batch=4, cpb=3, max_k=2,
            )

    def test_digit_words_straddle_tail_blocks(self):
        # 61-byte data + 3-digit nonces: digit bytes 62..64 span words
        # 15 (block 0) and 16 (block 1) — both tail blocks carry vector
        # words, the layout class where constant-folding must not leak.
        data = "s" * 61
        lay = build_layout(data.encode(), 3)
        words = {p.word for p in lay.digit_pos[1:]}  # k=2 low digits
        assert min(words) < 16 <= max(words), words
        r = sweep_min_hash(
            data, 100, 460, backend="pallas", interpret=True, batch=2, max_k=2
        )
        assert (r.hash, r.nonce) == min_hash_range(data, 100, 460)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_fuzz_data_lengths_and_ranges(self, seed):
        """Seeded fuzz over data lengths x range positions, specifically
        sampling shapes where the in-kernel digit words STRADDLE a tail
        block boundary (e.g. 57-byte data, 10-digit nonces -> words 15/16)
        — the layout class where both blocks carry vector words and the
        scalar constant-folding must not leak across blocks."""
        import random

        rng = random.Random(seed)
        for _ in range(4):
            dlen = rng.choice([0, 3, 54, 55, 56, 57, 58, 60, 61, 120])
            data = "f" * dlen
            d = rng.choice([2, 3])  # digit counts (k <= 2 keeps compiles fast)
            if rng.random() < 0.3:
                # Straddle the digit-class boundary: two classes, two
                # kernels (dyn shares one executable per k), one min-fold.
                lo = 10**d - rng.randint(5, 40)
                hi = 10**d + rng.randint(5, 40)
            else:
                lo = rng.randint(10 ** (d - 1), 10**d - 30)
                hi = min(lo + rng.randint(1, 150), 10**d - 1)
            r = sweep_min_hash(
                data, lo, hi, backend="pallas", interpret=True, batch=2, max_k=2
            )
            assert (r.hash, r.nonce) == min_hash_range(data, lo, hi), (
                dlen, d, lo, hi,
            )

    def test_argmin_index_overflow_rejected(self):
        # batch * 10^k beyond int32 would silently corrupt the flat argmin
        # index (measured wrong nonces at k=7/batch=1024 on TPU) — the
        # kernel builder must refuse the shape outright.
        from bitcoin_miner_tpu.ops.pallas_sha256 import make_pallas_minhash
        from bitcoin_miner_tpu.ops.sha256 import build_layout

        layout = build_layout(b"cmu440", 10)
        with pytest.raises(ValueError, match="int32"):
            make_pallas_minhash(
                layout.n_tail_blocks, layout.digit_pos[3:], 7, batch=1024
            )

    def test_tie_break_same_dispatch_lowest_nonce(self):
        # Two chunk rows covering the SAME nonce range in one dispatch tie
        # on (h0, h1) everywhere; the lane accumulator + final cross-lane
        # argmin must resolve to the lowest flat index -> lowest nonce.
        from bitcoin_miner_tpu.ops.pallas_sha256 import make_pallas_minhash
        from bitcoin_miner_tpu.ops.sha256 import build_layout
        import numpy as np

        layout = build_layout(b"tie", 3)
        k = 2
        fn = make_pallas_minhash(
            layout.n_tail_blocks, layout.digit_pos[1:], k,
            batch=2, interpret=True,
        )
        midstate = np.array(layout.midstate, dtype=np.uint32)
        row = np.array(layout.tail_template, dtype=np.uint64)
        dp = layout.digit_pos[0]
        row[dp.word] |= np.uint64(ord("1") << dp.shift)  # high digit '1'
        tailcb = np.tile(
            np.concatenate([row, [0, 100]]).astype(np.uint32), (2, 1)
        )
        h0, h1, idx = fn(midstate, tailcb)
        # Both rows are nonces [100, 199]; the winner must come from row 0.
        assert int(idx) < 10**k


class TestHostRouting:
    """Tiny digit classes route to the host tier (HostFold) instead of
    compiling a one-off device kernel — the r5 fix for ~14 s/class
    first-use stalls (tracing + executable load) in the mining app."""

    def test_sweep_min_hash_host_budget_matches_oracle(self):
        from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

        # Budget 10^4 routes d<=4 to the host; d=5 still goes to the device.
        r = sweep_min_hash(
            "cmu440", 7, 20002, backend="xla", max_k=2,
            host_lane_budget=10**4,
        )
        assert (r.hash, r.nonce) == min_hash_range("cmu440", 7, 20002)
        assert r.lanes_swept == 20002 - 7 + 1

    def test_host_routed_groups_skip_kernel_build(self):
        from bitcoin_miner_tpu.ops.sweep import run_sweep_dispatches, HostFold

        built, folds = [], []

        def get_kernel(layout, group):
            built.append(group.d)
            raise AssertionError("device kernel built for host-routed group")

        def consume(out, bases, n_lanes):
            assert isinstance(out, HostFold)
            folds.append((out.hash, out.nonce))

        lanes = run_sweep_dispatches(
            "cmu440", 7, 9999, max_k=2, batch=4,
            get_kernel=get_kernel, run_kernel=None, consume=consume,
            host_lane_budget=10**4,
        )
        assert not built
        assert lanes == 9999 - 7 + 1
        assert min(folds) == min_hash_range("cmu440", 7, 9999)

    def test_pipeline_auto_budget_matches_oracle(self):
        from bitcoin_miner_tpu.ops.sweep import SweepPipeline

        p = SweepPipeline(backend="xla", max_k=2)  # auto host budget
        try:
            r = p.submit("cmu440", 3, 1234).result(timeout=300)
            assert (r.hash, r.nonce) == min_hash_range("cmu440", 3, 1234)
            assert r.lanes_swept == 1234 - 3 + 1
        finally:
            p.close()

    def test_prewarm_async_dedupes_and_skips_host_classes(self):
        from bitcoin_miner_tpu.ops.sweep import (
            SweepPipeline,
            auto_host_lane_budget,
        )

        p = SweepPipeline(backend="xla", max_k=2)
        try:
            host_d = 1
            assert 10**host_d <= auto_host_lane_budget()
            assert p.prewarm_async("cmu440", host_d) is False  # host-routed
            assert p.prewarm_async("cmu440", 21) is False  # beyond u64
            assert p.prewarm_async("cmu440", 9) is True
            assert p.prewarm_async("cmu440", 9) is False  # already warming
            # A sweep through the prewarmed class still matches the oracle.
            r = p.submit("cmu440", 10**8, 10**8 + 500).result(timeout=300)
            assert (r.hash, r.nonce) == min_hash_range(
                "cmu440", 10**8, 10**8 + 500
            )
        finally:
            p.close()


    @pytest.mark.parametrize(
        "dyn_k,budget", [(6, 10**6), (2, 10**2), (8, 10**7), (None, 10**7)]
    )
    def test_auto_budget_leaves_dyn_classes_to_the_device(self, dyn_k, budget):
        # A dyn kernel serves every class d > dyn_k with no compile, so
        # only d <= dyn_k stays on the host.
        from bitcoin_miner_tpu import native
        from bitcoin_miner_tpu.ops.sweep import auto_host_lane_budget

        if not native.available():
            pytest.skip("native fold not built")
        assert auto_host_lane_budget(True, dyn_k) == budget
        assert auto_host_lane_budget(False, dyn_k) == min(budget, 10**5)

    @pytest.mark.parametrize(
        "kw,budget",
        [(dict(backend="pallas", interpret=True, max_k=2), 10**2),
         (dict(backend="pallas", interpret=True, max_k=2, factored=True),
          10**7),
         (dict(backend="xla", max_k=2), 10**7)],
        ids=["pallas-dyn", "pallas-factored", "xla"],
    )
    def test_pipeline_auto_budget_by_tier(self, kw, budget):
        from bitcoin_miner_tpu import native
        from bitcoin_miner_tpu.ops.sweep import SweepPipeline

        if not native.available():
            pytest.skip("native fold not built")
        p = SweepPipeline(batch=2, **kw)
        try:
            assert p._host_lane_budget == budget
        finally:
            p.close()

    def test_pallas_pipeline_sweeps_d_above_max_k_on_the_device(self):
        from bitcoin_miner_tpu.ops.sweep import SweepPipeline
        from bitcoin_miner_tpu.utils.metrics import METRICS

        p = SweepPipeline(backend="pallas", interpret=True, max_k=2, batch=2)
        try:
            assert p._host_lane_budget == 10**2
            host0 = METRICS.get("sweep.host_fold_lanes")
            dev0 = METRICS.get("sweep.device_lanes")
            r = p.submit("cmu440", 5, 130).result(timeout=300)
            assert (r.hash, r.nonce) == min_hash_range("cmu440", 5, 130)
            assert METRICS.get("sweep.host_fold_lanes") - host0 == 100 - 5
            assert METRICS.get("sweep.device_lanes") - dev0 == 130 - 100 + 1
        finally:
            p.close()


class TestDynKernel:
    """The digit-position-dynamic Pallas kernel: one executable serves all
    digit classes of a data length (contributions are runtime inputs)."""

    def test_one_executable_across_digit_classes(self):
        from bitcoin_miner_tpu.ops.sweep import _build_kernel, decompose_range
        from bitcoin_miner_tpu.ops.sha256 import build_layout

        kerns = []
        for d_lo in (10**7, 10**8, 10**9):
            group = next(decompose_range(d_lo, d_lo, max_k=6))
            layout = build_layout(b"cmu440", group.d)
            kerns.append(
                _build_kernel("pallas", 8, None, None, True, False, layout, group)
            )
        keys = {k.class_key for k in kerns}
        assert len(keys) == 1, "digit classes d=8..10 must share one kernel"

    @pytest.mark.parametrize("data", ["x", "cmu440", "abcdefgh"])
    def test_dyn_matches_oracle_across_phases(self, data):
        # Different data lengths shift digit_off mod 4 -> different window
        # alignments; each must stay bit-exact across a digit boundary.
        from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

        r = sweep_min_hash(
            data, 9985, 10015, backend="pallas", interpret=True, max_k=2, batch=4
        )
        assert (r.hash, r.nonce) == min_hash_range(data, 9985, 10015)

    def test_window_rejects_out_of_range_digit(self):
        from bitcoin_miner_tpu.ops.pallas_sha256 import window_contribs_np
        from bitcoin_miner_tpu.ops.sha256 import build_layout

        layout = build_layout(b"cmu440", 10)
        low_pos = layout.digit_pos[4:]
        with pytest.raises(ValueError, match="window"):
            window_contribs_np(6, low_pos, 0, 1, 1024)

    def test_d1_class_falls_back_to_static_kernel(self):
        # d=1 has d == k, one short of the dyn window's d >= k+1 domain
        # (digit_off=7 for 'cmu440' puts its digit in word 1, below w_lo=2)
        # — the driver must fall back to the per-class static kernel, not
        # raise.  Regression test for the r5 review finding.
        from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

        r = sweep_min_hash(
            "cmu440", 5, 15, backend="pallas", interpret=True,
            batch=2, max_k=2,
        )
        assert (r.hash, r.nonce) == min_hash_range("cmu440", 5, 15)

    @staticmethod
    def _window_ending_in_min(data, n_rows):
        """Start of the first window of ``n_rows`` d=4 chunks (k=2, 100
        nonces each) whose minimum lies in its last chunk, so a grid that
        stopped a group short would miss it."""
        mins = [min_hash_range(data, b, b + 99) for b in range(1000, 10000, 100)]
        for s in range(len(mins) - n_rows + 1):
            if min(mins[s : s + n_rows]) == mins[s + n_rows - 1]:
                return 1000 + 100 * s
        raise AssertionError(f"no {n_rows}-chunk window ends in its minimum")

    @pytest.mark.parametrize("sieve", [True, False], ids=["sieve", "full_fold"])
    @pytest.mark.parametrize(
        "case", ["1", "cpb-1", "cpb", "cpb+1", "batch", "tie"]
    )
    def test_runtime_group_count_bit_exact(self, case, sieve):
        """A grid over ceil(rows / cpb) row groups, the count the drivers
        pass, answers as the hashlib oracle and as the full batch // cpb
        grid.  "tie" repeats the minimum's row (the last, alone in the
        second group) in slot 0: the lower slot, the lower flat index,
        must win."""
        import numpy as np

        from bitcoin_miner_tpu.ops.pallas_sha256 import (
            DEFAULT_TILE, dyn_params, make_pallas_minhash_dyn,
            window_contribs_np,
        )
        from bitcoin_miner_tpu.ops.sweep import _fill_templates

        data, batch, cpb = "cmu440", 8, 4
        n_rows = {
            "1": 1, "cpb-1": cpb - 1, "cpb": cpb, "cpb+1": cpb + 1,
            "batch": batch, "tie": cpb + 1,
        }[case]
        lo = self._window_ending_in_min(data, n_rows)
        hi = lo + 100 * n_rows - 1
        group = next(decompose_range(lo, hi, max_k=2))
        chunks = list(group.chunks)
        if case == "tie":
            chunks[0] = chunks[-1]
        layout = build_layout(data.encode(), group.d)
        w_lo, w_hi = dyn_params(layout, group.k)
        fn, n_pad = make_pallas_minhash_dyn(
            layout.n_tail_blocks, w_lo, w_hi, group.k, batch, DEFAULT_TILE,
            interpret=True, cpb=cpb, sieve=sieve,
        )
        tail_const, bounds = _fill_templates(layout, group, chunks, batch)
        tailcb = np.concatenate([tail_const, bounds.astype(np.uint32)], axis=1)
        th = [np.array([0x7FFFFFFF], dtype=np.int32)] if sieve else []
        low_pos = layout.digit_pos[layout.digit_count - group.k :]
        contribs = window_contribs_np(group.k, low_pos, w_lo, w_hi, n_pad)
        midstate = np.array(layout.midstate, dtype=np.uint32)

        def run(n_groups):
            out = fn(midstate, tailcb, np.int32(n_groups), *th, *contribs)
            return tuple(int(x) for x in out)

        n_groups = -(-n_rows // cpb)
        got = run(n_groups)
        assert got == run(batch // cpb)
        h0, h1, idx = got
        nonce = chunks[idx // 100].base + idx % 100
        assert ((h0 << 32) | h1, nonce) == min_hash_range(data, lo, hi)
        if case == "tie":
            assert idx // 100 == 0
        elif n_groups > 1:
            # The extent is honoured: a group short, the minimum's row is
            # never hashed.
            assert run(n_groups - 1) != got

    def test_zero_tiles_shared_across_classes(self):
        from bitcoin_miner_tpu.ops.pallas_sha256 import (
            dyn_window, window_contribs_np, zero_tile_np,
        )
        from bitcoin_miner_tpu.ops.sha256 import build_layout

        zeros = set()
        for d in (8, 9, 10):
            layout = build_layout(b"cmu440", d)
            low_pos = layout.digit_pos[d - 6:]
            w_lo, w_hi = dyn_window(7, 16, 6)
            tiles = window_contribs_np(6, low_pos, w_lo, w_hi, 4096)
            zeros |= {id(t) for t in tiles if t is zero_tile_np(4096)}
        assert len(zeros) == 1, "untouched words must share ONE zero tile"


class TestSieve:
    """The two-stage sieve kernel (ISSUE 13): pass-1 survivor predicate
    ``h0 <= threshold`` + survivor-only pass-2 min-fold, on both backends.
    The adversarial matrix: exact ``h0 == threshold`` ties (which must
    conservatively survive), duplicate minimum hashes with the
    lowest-nonce tie-break, digit-class boundaries (9→10, 99→100), and
    the u64 upper edge — every case bit-exact vs the hashlib oracle."""

    BACKENDS = [
        ("xla", dict(backend="xla")),
        ("pallas", dict(backend="pallas", interpret=True, batch=2)),
    ]

    @pytest.mark.parametrize("name,kw", BACKENDS, ids=[b[0] for b in BACKENDS])
    @pytest.mark.parametrize(
        "lo,hi",
        [
            (5, 15),       # 9→10: d=1 (static pallas fallback) + d=2
            (93, 107),     # 99→100 digit-class boundary
            (985, 1040),   # 999→1000 (the dyn-kernel window shift)
        ],
    )
    def test_digit_class_boundaries(self, name, kw, lo, hi):
        r = sweep_min_hash("cmu440", lo, hi, max_k=2, sieve=True, **kw)
        assert (r.hash, r.nonce) == min_hash_range("cmu440", lo, hi)
        assert r.lanes_swept == hi - lo + 1

    @pytest.mark.parametrize("name,kw", BACKENDS, ids=[b[0] for b in BACKENDS])
    def test_u64_upper_edge(self, name, kw):
        top = (1 << 64) - 1
        r = sweep_min_hash("big", top - 50, top, max_k=1, sieve=True, **kw)
        assert (r.hash, r.nonce) == min_hash_range("big", top - 50, top)

    def test_multi_dispatch_threshold_tightens_bit_exact(self):
        # batch=2 at k=2 → many dispatches: later ones run against a
        # tightened running-min threshold and mostly skip pass 2; the
        # fold must stay bit-exact (cross-checked per-nonce below via
        # digest_u64_py so the layout machinery itself is in the loop).
        lo, hi = 100, 2099
        r = sweep_min_hash(
            "cmu440", lo, hi, backend="xla", max_k=2, batch=2, sieve=True
        )
        assert (r.hash, r.nonce) == min_hash_range("cmu440", lo, hi)
        best = None
        for n in range(lo, hi + 1):
            digits = str(n)
            layout = build_layout(b"cmu440", len(digits))
            cand = (digest_u64_py(layout, digits), n)
            if best is None or cand < best:
                best = cand
        assert (r.hash, r.nonce) == best

    # ---------------------------------------------------- direct kernel calls

    def _tie_setup(self):
        """One chunk row of nonces [100, 199] for data 'tie' (d=3, k=2)
        plus the oracle's (min h0, min h1, argmin lane) over it."""
        import numpy as np

        layout = build_layout(b"tie", 3)
        h, n = min_hash_range("tie", 100, 199)
        row = np.array(layout.tail_template, dtype=np.uint64)
        dp = layout.digit_pos[0]
        row[dp.word] |= np.uint64(ord("1") << dp.shift)
        midstate = np.array(layout.midstate, dtype=np.uint32)
        return layout, midstate, row, (h >> 32, h & 0xFFFFFFFF, n - 100)

    def test_xla_threshold_tie_survives(self):
        """``h0 == threshold`` exactly: the tie must survive pass 1 —
        a strict predicate would lose a lane that still wins on (h1,
        nonce)."""
        import jax.numpy as jnp
        import numpy as np

        from bitcoin_miner_tpu.ops.sweep import make_kernel_body

        layout, midstate, row, (eh0, eh1, elane) = self._tie_setup()
        kern = make_kernel_body(
            layout.n_tail_blocks, layout.digit_pos[1:], 2, batch=1,
            rolled=True, sieve=True,
        )
        tail_const = row.astype(np.uint32)[None, :]
        bounds = np.array([[0, 100]], dtype=np.int32)
        h0, h1, idx = kern(
            jnp.asarray(midstate), jnp.asarray(tail_const),
            jnp.asarray(bounds), jnp.uint32(eh0),  # thresh == exact min h0
        )
        assert (int(h0), int(h1), int(idx)) == (eh0, eh1, elane)

    def test_xla_threshold_below_min_prunes_everything(self):
        """threshold strictly below the range's min h0: no survivor, the
        I32_MAX sentinel comes back, and the host keeps its running best
        — proves the sieve actually prunes rather than vacuously passing."""
        import jax.numpy as jnp
        import numpy as np

        from bitcoin_miner_tpu.ops.sweep import I32_MAX, make_kernel_body

        layout, midstate, row, (eh0, _eh1, _elane) = self._tie_setup()
        assert eh0 > 0, "degenerate oracle minimum"
        kern = make_kernel_body(
            layout.n_tail_blocks, layout.digit_pos[1:], 2, batch=1,
            rolled=True, sieve=True,
        )
        tail_const = row.astype(np.uint32)[None, :]
        bounds = np.array([[0, 100]], dtype=np.int32)
        _h0, _h1, idx = kern(
            jnp.asarray(midstate), jnp.asarray(tail_const),
            jnp.asarray(bounds), jnp.uint32(eh0 - 1),
        )
        assert int(idx) == I32_MAX

    def test_pallas_sieve_threshold_tie_survives(self):
        """Same tie contract through the REAL prize path: the pallas
        sieve kernel's SMEM threshold scratch + survivor-only pass 2."""
        import numpy as np

        from bitcoin_miner_tpu.ops.pallas_sha256 import make_pallas_minhash

        layout, midstate, row, (eh0, eh1, elane) = self._tie_setup()
        fn = make_pallas_minhash(
            layout.n_tail_blocks, layout.digit_pos[1:], 2,
            batch=1, interpret=True, sieve=True,
        )
        tailcb = np.concatenate([row, [0, 100]]).astype(np.uint32)[None, :]
        thresh = np.array([eh0 ^ 0x80000000], dtype=np.uint32).view(np.int32)
        h0, h1, idx = fn(midstate, tailcb, thresh)
        assert (int(h0), int(h1), int(idx)) == (eh0, eh1, elane)
        # And strictly below the min: everything pruned.
        from bitcoin_miner_tpu.ops.sweep import I32_MAX

        thresh = np.array([(eh0 - 1) ^ 0x80000000], dtype=np.uint32).view(
            np.int32
        )
        _h0, _h1, idx = fn(midstate, tailcb, thresh)
        assert int(idx) == I32_MAX

    def test_pallas_sieve_duplicate_minimum_lowest_nonce(self):
        """Duplicate rows covering the same range tie on (h0, h1)
        everywhere; the sieve kernel's pass 2 must still resolve to the
        lowest flat index → lowest nonce (same contract as the baseline
        kernel's tie tests above)."""
        import numpy as np

        from bitcoin_miner_tpu.ops.pallas_sha256 import make_pallas_minhash

        layout, midstate, row, (eh0, eh1, _elane) = self._tie_setup()
        fn = make_pallas_minhash(
            layout.n_tail_blocks, layout.digit_pos[1:], 2,
            batch=2, cpb=2, interpret=True, sieve=True,
        )
        tailcb = np.tile(
            np.concatenate([row, [0, 100]]).astype(np.uint32), (2, 1)
        )
        thresh = np.array([0xFFFFFFFF ^ 0x80000000], dtype=np.uint32).view(
            np.int32
        )  # loose: everything survives, both duplicate rows fold
        h0, h1, idx = fn(midstate, tailcb, thresh)
        assert (int(h0), int(h1)) == (eh0, eh1)
        assert int(idx) < 100  # row 0, not the duplicate row 1


class TestFactored:
    """Factored-nonce compression (ISSUE 14): outer/inner digit
    decomposition with a per-group scalar round prefix, on BOTH
    backends, plain and composed with the PR-13 sieve.  The adversarial
    matrix mirrors TestSieve's: digit-class boundaries (9→10, 99→100,
    999→1000), the u64 upper edge (where k=1 leaves nothing to factor
    and the baseline fallback must ride along silently), duplicate
    minima with the lowest-nonce tie-break through the factored pallas
    kernel, threshold ties/prunes through its SMEM scratch, and a
    multi-dispatch leg cross-checked per-nonce against digest_u64_py —
    every case bit-exact."""

    BACKENDS = [
        ("xla", dict(backend="xla")),
        ("pallas", dict(backend="pallas", interpret=True, batch=2)),
    ]

    @pytest.mark.parametrize("name,kw", BACKENDS, ids=[b[0] for b in BACKENDS])
    @pytest.mark.parametrize(
        "lo,hi",
        [
            (5, 15),       # 9→10: d=1 (k=1 → unfactorable fallback) + d=2
            (93, 107),     # 99→100 digit-class boundary
            (985, 1040),   # 999→1000
        ],
    )
    def test_digit_class_boundaries(self, name, kw, lo, hi):
        r = sweep_min_hash(
            "cmu440", lo, hi, max_k=2, factored=True, sieve=False, **kw
        )
        assert (r.hash, r.nonce) == min_hash_range("cmu440", lo, hi)
        assert r.lanes_swept == hi - lo + 1

    @pytest.mark.parametrize("name,kw", BACKENDS, ids=[b[0] for b in BACKENDS])
    @pytest.mark.parametrize("lo,hi", [(93, 107), (985, 1040)])
    def test_factored_sieve_composition(self, name, kw, lo, hi):
        # Pass 1 h0-only AND pass 2 resume from ONE shared group prefix.
        r = sweep_min_hash(
            "cmu440", lo, hi, max_k=2, factored=True, sieve=True, **kw
        )
        assert (r.hash, r.nonce) == min_hash_range("cmu440", lo, hi)

    @pytest.mark.parametrize("name,kw", BACKENDS, ids=[b[0] for b in BACKENDS])
    def test_u64_upper_edge(self, name, kw):
        top = (1 << 64) - 1
        r = sweep_min_hash(
            "big", top - 50, top, max_k=1, factored=True, sieve=True, **kw
        )
        assert (r.hash, r.nonce) == min_hash_range("big", top - 50, top)

    def test_multi_dispatch_threshold_tightens_bit_exact(self):
        # Factored + sieve over many dispatches: the threshold tightens
        # host-side between dispatches AND across the group loop inside
        # each; the fold must stay bit-exact per-nonce via digest_u64_py
        # (the layout machinery itself in the loop, like TestSieve's).
        lo, hi = 100, 2099
        r = sweep_min_hash(
            "cmu440", lo, hi, backend="xla", max_k=2, batch=2,
            factored=True, sieve=True,
        )
        best = None
        for n in range(lo, hi + 1):
            digits = str(n)
            layout = build_layout(b"cmu440", len(digits))
            cand = (digest_u64_py(layout, digits), n)
            if best is None or cand < best:
                best = cand
        assert (r.hash, r.nonce) == best

    # ---------------------------------------------------- direct kernel calls

    def _tie_setup(self):
        """Same fixture as TestSieve: one chunk row of [100, 199] for
        'tie' (d=3, k=2 → k_in=1, 10 outer groups of 10 lanes)."""
        import numpy as np

        layout = build_layout(b"tie", 3)
        h, n = min_hash_range("tie", 100, 199)
        row = np.array(layout.tail_template, dtype=np.uint64)
        dp = layout.digit_pos[0]
        row[dp.word] |= np.uint64(ord("1") << dp.shift)
        midstate = np.array(layout.midstate, dtype=np.uint32)
        return layout, midstate, row, (h >> 32, h & 0xFFFFFFFF, n - 100)

    def test_pallas_factored_threshold_tie_survives_and_prunes(self):
        """h0 == threshold survives pass 1 through the factored sieve
        kernel's per-group scratch path; threshold strictly below the
        min prunes every group to the sentinel."""
        import numpy as np

        from bitcoin_miner_tpu.ops.pallas_sha256 import (
            make_pallas_minhash_factored,
        )

        layout, midstate, row, (eh0, eh1, elane) = self._tie_setup()
        fn = make_pallas_minhash_factored(
            layout.n_tail_blocks, layout.digit_pos[1:], 2, 1,
            batch=1, interpret=True, sieve=True,
        )
        tailcb = np.concatenate([row, [0, 100]]).astype(np.uint32)[None, :]
        thresh = np.array([eh0 ^ 0x80000000], dtype=np.uint32).view(np.int32)
        h0, h1, idx = fn(midstate, tailcb, thresh)
        assert (int(h0), int(h1), int(idx)) == (eh0, eh1, elane)
        from bitcoin_miner_tpu.ops.sweep import I32_MAX

        thresh = np.array([(eh0 - 1) ^ 0x80000000], dtype=np.uint32).view(
            np.int32
        )
        _h0, _h1, idx = fn(midstate, tailcb, thresh)
        assert int(idx) == I32_MAX

    def test_pallas_factored_duplicate_minimum_lowest_nonce(self):
        """Duplicate rows tie on (h0, h1) everywhere; the factored
        kernel's remapped global flat index must still resolve to row 0
        — the outer/inner remap cannot reorder the tie-break."""
        import numpy as np

        from bitcoin_miner_tpu.ops.pallas_sha256 import (
            make_pallas_minhash_factored,
        )

        layout, midstate, row, (eh0, eh1, _elane) = self._tie_setup()
        fn = make_pallas_minhash_factored(
            layout.n_tail_blocks, layout.digit_pos[1:], 2, 1,
            batch=2, cpb=2, interpret=True, sieve=False,
        )
        tailcb = np.tile(
            np.concatenate([row, [0, 100]]).astype(np.uint32), (2, 1)
        )
        h0, h1, idx = fn(midstate, tailcb)
        assert (int(h0), int(h1)) == (eh0, eh1)
        assert int(idx) < 100  # row 0, not the duplicate row 1

    def test_xla_factored_matches_direct_kernel(self):
        """The factored xla kernel body called directly (the sharded
        tier re-traces exactly this fn inside shard_map) agrees with the
        oracle's (h0, h1, lane) triple, runt bounds included."""
        import jax.numpy as jnp
        import numpy as np

        from bitcoin_miner_tpu.ops.sweep import make_kernel_body

        layout, midstate, row, _ = self._tie_setup()
        h, n = min_hash_range("tie", 130, 169)  # runt inside the chunk
        kern = make_kernel_body(
            layout.n_tail_blocks, layout.digit_pos[1:], 2, batch=1,
            rolled=True, factored=1,
        )
        tail_const = row.astype(np.uint32)[None, :]
        bounds = np.array([[30, 70]], dtype=np.int32)
        h0, h1, idx = kern(
            jnp.asarray(midstate), jnp.asarray(tail_const), jnp.asarray(bounds)
        )
        assert (int(h0), int(h1), int(idx)) == (h >> 32, h & 0xFFFFFFFF, n - 100)


class TestPipelineLifecycle:
    """SweepPipeline edge behavior: close/submit ordering and concurrent
    submitters — the states a miner hits at shutdown and under the
    scheduler's 2-deep window."""

    def test_submit_after_close_raises(self):
        from bitcoin_miner_tpu.ops.sweep import SweepPipeline

        p = SweepPipeline(backend="xla", max_k=2, batch=2)
        p.close()
        with pytest.raises(RuntimeError, match="closed"):
            p.submit("cmu440", 0, 10)

    def test_jobs_submitted_before_close_still_resolve(self):
        from bitcoin_miner_tpu.ops.sweep import SweepPipeline

        p = SweepPipeline(backend="xla", max_k=2, batch=2, host_lane_budget=0)
        futs = [p.submit("cmu440", 1000 + 100 * i, 1099 + 100 * i)
                for i in range(3)]
        p.close()  # close() drains queued jobs, it does not abandon them
        for i, f in enumerate(futs):
            lo, hi = 1000 + 100 * i, 1099 + 100 * i
            r = f.result(timeout=300)
            assert (r.hash, r.nonce) == min_hash_range("cmu440", lo, hi)

    def test_concurrent_submitters_all_correct(self):
        import threading

        from bitcoin_miner_tpu.ops.sweep import SweepPipeline

        p = SweepPipeline(backend="xla", max_k=2, batch=2, host_lane_budget=0)
        results = {}
        lock = threading.Lock()

        def worker(i):
            lo, hi = 2000 + 137 * i, 2000 + 137 * i + 99
            r = p.submit("cmu440", lo, hi).result(timeout=300)
            with lock:
                results[i] = ((r.hash, r.nonce), min_hash_range("cmu440", lo, hi))

        try:
            ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
                assert not t.is_alive()
        finally:
            p.close()
        assert len(results) == 6
        for got, want in results.values():
            assert got == want


@pytest.mark.workloads
class TestBlake2bDeviceTier:
    """The second kernel family (ISSUE 20): the u32-pair BLAKE2b-64
    device kernel vs the workload's hashlib oracle.  The adversarial
    matrix mirrors TestSieve/TestFactored's: digit-class boundaries
    (9→10, 99→100, 999→1000), the u64 upper edge, duplicate minima with
    the lowest-nonce tie-break through a direct kernel call, a
    multi-dispatch leg cross-checked per-nonce against the pure-Python
    compression (the layout machinery itself in the loop), and the
    watchdog downgrade drill across the family's xla→cpu→hashlib chain."""

    @staticmethod
    def _wl():
        from bitcoin_miner_tpu import workloads

        return workloads.get("blake2b64")

    @pytest.mark.parametrize(
        "lo,hi",
        [
            (5, 15),       # 9→10: d=1 and d=2 classes in one sweep
            (93, 107),     # 99→100 digit-class boundary
            (985, 1040),   # 999→1000
        ],
    )
    def test_digit_class_boundaries(self, lo, hi):
        w = self._wl()
        r = sweep_min_hash("cmu440", lo, hi, backend="xla", max_k=2, workload=w)
        assert (r.hash, r.nonce) == w.min_range("cmu440", lo, hi)
        assert r.lanes_swept == hi - lo + 1

    def test_u64_upper_edge(self):
        w = self._wl()
        top = (1 << 64) - 1
        r = sweep_min_hash(
            "big", top - 50, top, backend="xla", max_k=1, workload=w
        )
        assert (r.hash, r.nonce) == w.min_range("big", top - 50, top)

    def test_sieve_threshold_operand_bit_exact(self):
        # The kernel's threshold mask (off by default for this family)
        # must stay bit-exact when forced on.
        w = self._wl()
        r = sweep_min_hash(
            "cmu440", 93, 320, backend="xla", max_k=2, sieve=True, workload=w
        )
        assert (r.hash, r.nonce) == w.min_range("cmu440", 93, 320)

    @pytest.mark.parametrize("dlen", [126, 250])
    def test_tail_shape_classes_bit_exact(self, dlen):
        """The family's two adversarial tail shapes beyond the short-data
        tests above: a message straddling the 128-byte block boundary
        (digit bytes land past byte 128 → two tail blocks), and a prefix
        long enough that whole blocks fold into the midstate host-side.
        Each data LENGTH is its own compiled shape class, so two lengths
        buy the coverage without a compile per fuzz draw."""
        w = self._wl()
        data = "f" * dlen
        r = sweep_min_hash(data, 93, 107, backend="xla", max_k=2, workload=w)
        assert (r.hash, r.nonce) == w.min_range(data, 93, 107)

    def test_multi_dispatch_cross_checked_per_nonce(self):
        # batch=2 at k=2 → many dispatches across two digit classes; the
        # fold must agree per-nonce with the pure-Python compression
        # (digest64_py), putting the blake2b layout machinery itself in
        # the loop rather than trusting hashlib's message assembly.
        from bitcoin_miner_tpu.ops.blake2b import digest64_py

        w = self._wl()
        lo, hi = 100, 1299
        r = sweep_min_hash(
            "cmu440", lo, hi, backend="xla", max_k=2, batch=2, workload=w
        )
        best = None
        for n in range(lo, hi + 1):
            cand = (digest64_py(b"cmu440 " + str(n).encode()), n)
            if best is None or cand < best:
                best = cand
        assert (r.hash, r.nonce) == best

    def test_duplicate_minimum_lowest_nonce(self):
        """Duplicate chunk rows covering the same range tie on (h0, h1)
        everywhere; the kernel's flat argmin (and the factored remap
        behind it) must resolve to row 0 → the lowest nonce."""
        import jax.numpy as jnp
        import numpy as np

        from bitcoin_miner_tpu.ops.blake2b import (
            build_layout as b2_layout,
            make_blake2b_kernel_body,
        )

        w = self._wl()
        layout = b2_layout(b"tie", 3)
        h, n = w.min_range("tie", 100, 199)
        kern = make_blake2b_kernel_body(
            layout.msg_len, layout.tail_off, layout.n_tail_blocks,
            layout.live_words, layout.digit_pos[1:], 2, batch=2,
        )
        row = np.array(layout.tail_template, dtype=np.uint32)
        dp = layout.digit_pos[0]
        row[dp.word] |= np.uint32(ord("1") << dp.shift)  # high digit '1'
        tail_const = np.tile(row, (2, 1))
        bounds = np.array([[0, 100], [0, 100]], dtype=np.int32)
        midstate = np.array(layout.midstate, dtype=np.uint32)
        h0, h1, idx = kern(
            jnp.asarray(midstate), jnp.asarray(tail_const), jnp.asarray(bounds)
        )
        assert (int(h0), int(h1)) == (h >> 32, h & 0xFFFFFFFF)
        # Both rows are nonces [100, 199]; the winner must be row 0.
        assert int(idx) == n - 100

    def test_wedge_dispatch_downgrades_xla_to_cpu(self, monkeypatch):
        """The watchdog drill across the family's NEW 3-rung chain:
        ``BMT_WEDGE_DISPATCH=1`` hangs the blake2b xla pipeline's first
        fetch; the watchdog abandons the device rung and the chunk
        re-runs bit-exact on the cpu rung — hashlib still behind it."""
        from bitcoin_miner_tpu.apps import miner as miner_mod
        from bitcoin_miner_tpu.ops import sweep as sweep_mod
        from bitcoin_miner_tpu.utils.metrics import METRICS

        w = self._wl()
        monkeypatch.setenv("BMT_WEDGE_DISPATCH", "1")
        monkeypatch.setitem(sweep_mod._WEDGE_STATE, "fired", False)
        downgrades0 = METRICS.get("miner.tier_downgrades")
        ts = miner_mod._TieredSearch(
            [
                ("xla", lambda: w.make_async_search("xla")),
                ("cpu", lambda: w.make_async_search("cpu")),
                ("hashlib", lambda: w.min_range),
            ],
            wedge_seconds=4.0,
        )
        try:
            fut = ts.submit("b2wedge", 0, 120)
            assert fut.result(timeout=120) == w.min_range("b2wedge", 0, 120)
            assert ts.active_tier == "cpu"
            assert METRICS.get("miner.tier_downgrades") - downgrades0 == 1
            assert sweep_mod._WEDGE_STATE["fired"]  # the hang was real
        finally:
            ts.close()
