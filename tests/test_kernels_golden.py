"""Golden tests: Pallas interpret mode vs ref.py on odd shapes.

The allclose sweeps in test_kernels.py cover friendly shapes; these pin the
edge geometry the sharded pipeline actually produces — length-1 sequences,
batches that are not a multiple of the block size (shard-local pair buffers
are capacity-planned, not tile-aligned), and degenerate all-identical
inputs — for the trajectory kernels {lcs, minhash, shingle} and the
sorted-slab probe/merge kernels of the in-mesh streaming join.

The LCS cases force ``mode="interpret"`` so the kernel body really executes
(the "auto" dispatch would route tiny batches to the wavefront).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import PAD_KEY


def _sentinel_pad(a, b, la, lb):
    L = a.shape[1]
    a = a.copy()
    b = b.copy()
    a[np.arange(L)[None, :] >= la[:, None]] = -1
    b[np.arange(L)[None, :] >= lb[:, None]] = -2
    return a, b


class TestLCSGolden:
    def _check(self, a, b):
        from repro.kernels.lcs.ops import lcs
        from repro.kernels.lcs.ref import lcs as ref

        got = np.asarray(lcs(jnp.asarray(a), jnp.asarray(b), mode="interpret"))
        want = np.asarray(ref(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("B", [1, 3, 257])
    def test_length_one_sequences(self, B):
        rng = np.random.default_rng(B)
        L = 8
        a = rng.integers(0, 5, size=(B, L)).astype(np.int32)
        b = rng.integers(0, 5, size=(B, L)).astype(np.int32)
        a, b = _sentinel_pad(a, b, np.ones(B, int), np.ones(B, int))
        self._check(a, b)

    def test_max_len_one(self):
        # L == 1: the rolling window degenerates to a single lane
        a = np.asarray([[2], [3], [4]], np.int32)
        b = np.asarray([[2], [5], [4]], np.int32)
        self._check(a, b)

    @pytest.mark.parametrize("B", [5, 130, 300])
    def test_non_multiple_of_block_batches(self, B):
        rng = np.random.default_rng(B * 3)
        L = 12
        la = rng.integers(1, L + 1, size=B)
        lb = rng.integers(1, L + 1, size=B)
        a = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        b = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        a, b = _sentinel_pad(a, b, la, lb)
        self._check(a, b)

    def test_all_identical_inputs(self):
        B, L = 64, 10
        a = np.full((B, L), 7, np.int32)
        b = np.full((B, L), 7, np.int32)
        self._check(a, b)          # LCS == L for every row
        la = np.arange(B) % L + 1
        a2, b2 = _sentinel_pad(a, b, la, np.full(B, L, int))
        self._check(a2, b2)        # LCS == la: prefix vs full repeat


class TestLCSBlockPad:
    """The lcs_pallas wrapper pads any batch to whole blocks: one block of
    its own rows up to 1,024 pairs, whole [8, 128] vregs past that (1,500
    pairs are 12 rows, padded to 16)."""

    @pytest.mark.parametrize("B", [1, 5, 7, 130, 1500])
    def test_direct_kernel_any_batch(self, B):
        from repro.kernels.lcs.kernel import lcs_pallas
        from repro.kernels.lcs.ref import lcs as ref

        rng = np.random.default_rng(B)
        L = 10
        la = rng.integers(1, L + 1, size=B)
        lb = rng.integers(1, L + 1, size=B)
        a = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        b = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        a, b = _sentinel_pad(a, b, la, lb)
        got = np.asarray(
            lcs_pallas(jnp.asarray(a), jnp.asarray(b), interpret=True)
        )
        want = np.asarray(ref(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_array_equal(got, want)


class TestBlockFor:
    """kernel.block_rows sizes the lane-dense grid: a batch of at most one
    vreg of rows is one block of exactly its own [*, 128] rows (no padding
    to a full vreg tile); a larger batch is one [8, 128] vreg per block,
    its rows padded to whole vregs."""

    def test_waste_minimization(self):
        from repro.kernels.lcs.kernel import block_rows

        assert block_rows(513) == (5, 5)        # 640 lanes, one block
        assert block_rows(1) == (1, 1)          # one row of 128
        assert block_rows(1024) == (8, 8)       # exact fit
        assert block_rows(1025) == (8, 16)      # second block
        assert block_rows(8192) == (8, 64)      # exact multiple

    @pytest.mark.parametrize("pairs,padded", [
        (1500, 16), (9000, 72), (10_000, 80), (1 << 20, 8192),
    ])
    def test_rows_pad_to_whole_vregs(self, pairs, padded):
        from repro.kernels.lcs.kernel import SUBLANES, block_rows

        tr, rows = block_rows(pairs)
        assert (tr, rows) == (SUBLANES, padded)
        assert rows * 128 >= pairs and rows % tr == 0

    @pytest.mark.parametrize("B", [513, 640, 1000])
    def test_golden_at_non_pow2_batches(self, B):
        # the waste-minimized tile must stay bit-identical to the reference
        from repro.kernels.lcs.ops import lcs
        from repro.kernels.lcs.ref import lcs as ref

        rng = np.random.default_rng(B)
        L = 10
        la = rng.integers(1, L + 1, size=B)
        lb = rng.integers(1, L + 1, size=B)
        a = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        b = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        a, b = _sentinel_pad(a, b, la, lb)
        got = np.asarray(lcs(jnp.asarray(a), jnp.asarray(b), mode="interpret"))
        want = np.asarray(ref(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_array_equal(got, want)


class TestKernelImplNames:
    """Only the fused names reach the Pallas kernel, through the
    lane-dense table-indexed wrappers (kernels/lcs/fused.py); the removed
    row-path names are refused, not silently mapped."""

    @pytest.mark.parametrize("name,mode", [
        ("fused", "auto"), ("fused-pallas", "pallas"),
        ("fused-interpret", "interpret"),
    ])
    def test_kernel_names_are_dispatch_modes(self, name, mode):
        from repro.core.similarity import lcs_impl

        assert lcs_impl(name) == mode

    @pytest.mark.parametrize("name", ["kernel", "pallas", "pallas-interpret"])
    def test_row_path_names_are_refused(self, name):
        from repro.api import validate_lcs_impl
        from repro.core.similarity import lcs_impl

        with pytest.raises(ValueError, match="unknown lcs_impl"):
            validate_lcs_impl(name)
        with pytest.raises(ValueError, match="unknown lcs_impl"):
            lcs_impl(name)


class TestFusedGolden:
    """The fused gather-and-score kernel vs its jnp gather-then-score
    oracle: bit-identical level_lcs AND mss on the edge geometry."""

    def _world(self, N, H, L, P, seed=0):
        rng = np.random.default_rng(seed)
        lengths = rng.integers(1, L + 1, size=N).astype(np.int32)
        codes = rng.integers(0, 6, size=(N, H, L)).astype(np.int32)
        # the table carries PAD_CODE_A pads, as encode_codes produces
        pad = np.arange(L)[None, None, :] >= lengths[:, None, None]
        codes = np.where(pad, -1, codes)
        left = rng.integers(0, N, size=P).astype(np.int32)
        right = rng.integers(0, N, size=P).astype(np.int32)
        betas = rng.random(H).astype(np.float32)
        return tuple(map(jnp.asarray, (codes, lengths, left, right, betas)))

    def _check(self, codes, lengths, left, right, betas,
               codes_b=None, lengths_b=None):
        from repro.kernels.lcs.fused import (
            fused_gather_score, fused_score, fused_score_ref,
        )

        tb = codes if codes_b is None else codes_b
        lb = lengths if lengths_b is None else lengths_b
        want_lvl, want_mss = fused_score_ref(
            codes, lengths, tb, lb, left, right, betas
        )
        # the dispatch wrapper (the pipeline's path): bit-identical mss
        got_lvl, got_mss = fused_score(
            codes, lengths, tb, lb, left, right, betas, mode="interpret"
        )
        np.testing.assert_array_equal(np.asarray(got_lvl), np.asarray(want_lvl))
        np.testing.assert_array_equal(np.asarray(got_mss), np.asarray(want_mss))
        # the raw kernel's fused MSS epilogue: integer levels identical,
        # float epilogue within 1 ulp of the XLA lowering (FMA contraction)
        raw_lvl, raw_mss = fused_gather_score(
            codes, lengths, tb, lb, left, right, betas, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(raw_lvl), np.asarray(want_lvl))
        np.testing.assert_allclose(
            np.asarray(raw_mss), np.asarray(want_mss), rtol=1e-6
        )

    @pytest.mark.parametrize("P", [1, 3, 37])
    def test_odd_pair_counts(self, P):
        self._check(*self._world(N=11, H=3, L=9, P=P, seed=P))

    @pytest.mark.parametrize("H", [1, 2, 4])
    def test_level_counts(self, H):
        self._check(*self._world(N=9, H=H, L=8, P=13, seed=H))

    def test_length_one_rows(self):
        codes, lengths, left, right, betas = self._world(8, 3, 7, 16, seed=2)
        lengths = jnp.ones_like(lengths)
        codes = jnp.where(
            jnp.arange(7)[None, None, :] < 1, codes, -1
        )
        self._check(codes, lengths, left, right, betas)

    def test_all_identical_rows(self):
        N, H, L, P = 6, 2, 8, 10
        codes = jnp.full((N, H, L), 4, jnp.int32)
        lengths = jnp.full((N,), L, jnp.int32)
        left = jnp.arange(P, dtype=jnp.int32) % N
        right = (jnp.arange(P, dtype=jnp.int32) + 1) % N
        betas = jnp.asarray([0.25, 0.75], jnp.float32)
        self._check(codes, lengths, left, right, betas)
        lvl, _ = __import__(
            "repro.kernels.lcs.fused", fromlist=["fused_gather_score"]
        ).fused_gather_score(
            codes, lengths, codes, lengths, left, right, betas, interpret=True
        )
        assert (np.asarray(lvl) == L).all()

    def test_two_distinct_tables_iota_indices(self):
        """The shuffle-mode calling convention: two operand stacks with
        iota indices instead of one shared table with pair indices."""
        codes_a, len_a, left, right, betas = self._world(14, 3, 9, 14, seed=5)
        codes_b, len_b, _, _, _ = self._world(14, 3, 9, 14, seed=6)
        iota = jnp.arange(14, dtype=jnp.int32)
        self._check(codes_a, len_a, iota, iota, betas,
                    codes_b=codes_b, lengths_b=len_b)


class TestMinhashGolden:
    def _check(self, types, lengths, num_perm=8):
        from repro.kernels.minhash.ops import minhash_signatures as kern
        from repro.kernels.minhash.ref import minhash_signatures as ref

        got = np.asarray(kern(jnp.asarray(types), jnp.asarray(lengths),
                              num_perm=num_perm, block_b=64))
        want = np.asarray(ref(jnp.asarray(types), jnp.asarray(lengths),
                              num_perm=num_perm))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("N", [1, 67, 130])
    def test_non_multiple_of_block_batches(self, N):
        rng = np.random.default_rng(N)
        L = 10
        lengths = rng.integers(1, L + 1, size=N).astype(np.int32)
        types = rng.integers(0, 30, size=(N, L)).astype(np.int32)
        self._check(types, lengths)

    def test_length_one_sequences(self):
        N, L = 33, 12
        rng = np.random.default_rng(9)
        types = rng.integers(0, 30, size=(N, L)).astype(np.int32)
        self._check(types, np.ones(N, np.int32))

    def test_all_identical_inputs(self):
        N, L = 50, 8
        types = np.full((N, L), 4, np.int32)
        lengths = np.full((N,), L, np.int32)
        self._check(types, lengths)
        # identical sets => identical signatures across rows
        from repro.kernels.minhash.ops import minhash_signatures as kern

        sig = np.asarray(kern(jnp.asarray(types), jnp.asarray(lengths),
                              num_perm=8, block_b=64))
        assert (sig == sig[0]).all()


class TestShingleGolden:
    def _sets(self, keys):
        return [set(row[row != PAD_KEY].tolist()) for row in np.asarray(keys)]

    def _check(self, types, lengths, k=3, Q=30):
        from repro.core.shingling import shingles_from_types
        from repro.kernels.shingle.ops import shingle_keys

        got = shingle_keys(jnp.asarray(types), jnp.asarray(lengths),
                           k=k, num_types=Q, block_b=32)
        want = shingles_from_types(jnp.asarray(types), jnp.asarray(lengths),
                                   k=k, num_types=Q)
        assert self._sets(got) == self._sets(want)

    @pytest.mark.parametrize("N", [1, 33, 70])
    def test_non_multiple_of_block_batches(self, N):
        rng = np.random.default_rng(N * 7)
        L = 10
        lengths = rng.integers(1, L + 1, size=N).astype(np.int32)
        types = rng.integers(0, 30, size=(N, L)).astype(np.int32)
        self._check(types, lengths)

    def test_below_shingle_order_yields_empty(self):
        # length < k: no k-shingle exists; both sides must agree on "empty"
        N, L = 17, 8
        rng = np.random.default_rng(3)
        types = rng.integers(0, 30, size=(N, L)).astype(np.int32)
        lengths = np.full((N,), 2, np.int32)   # k = 3 below
        from repro.kernels.shingle.ops import shingle_keys

        got = shingle_keys(jnp.asarray(types), jnp.asarray(lengths),
                           k=3, num_types=30, block_b=32)
        assert all(s == set() for s in self._sets(got))
        self._check(types, lengths)

    def test_all_identical_inputs(self):
        # one distinct symbol -> exactly one distinct shingle after dedup
        N, L = 21, 9
        types = np.full((N, L), 5, np.int32)
        lengths = np.full((N,), L, np.int32)
        self._check(types, lengths)
        from repro.kernels.shingle.ops import shingle_keys

        keys = shingle_keys(jnp.asarray(types), jnp.asarray(lengths),
                            k=3, num_types=30, block_b=32)
        assert all(len(s) == 1 for s in self._sets(keys))


class TestSortedSlabGolden:
    """Golden shapes for the sorted-merge probe/insert kernels backing the
    in-mesh streaming join (core/device_index.py), pinned to the numpy
    bucket-semantics references on the geometries the shard program
    actually produces: PAD-only route buffers, a single-key world (every
    entry in one bucket), an exactly-full slab at the capacity boundary,
    and overflow-drop accounting."""

    def _slab(self, entries, cap):
        from repro.core.types import PAD_ID

        k = np.full((cap,), PAD_KEY, np.int32)
        r = np.full((cap,), PAD_ID, np.int32)
        for i, (key, rid) in enumerate(sorted(entries)):
            k[i], r[i] = key, rid
        return k, r

    def _check_probe(self, slab_k, slab_r, keys, rows, nn_cap=64, no_cap=64):
        from repro.core.device_index import probe_pairs, probe_pairs_ref
        from repro.core.types import PAD_ID

        lo, hi, examined, ovf = probe_pairs(
            jnp.asarray(slab_k), jnp.asarray(slab_r),
            jnp.asarray(keys), jnp.asarray(rows),
            nn_cap=nn_cap, no_cap=no_cap,
        )
        lo, hi = np.asarray(lo), np.asarray(hi)
        got = sorted((int(a), int(b))
                     for a, b in zip(lo, hi) if a != PAD_ID)
        want, examined_want = probe_pairs_ref(slab_k, slab_r, keys, rows)
        assert int(ovf) == 0
        assert got == sorted(want)
        assert int(examined) == examined_want
        return examined_want

    def _check_merge(self, slab_k, slab_r, keys, rows):
        from repro.core.device_index import merge_insert, merge_insert_ref

        mk, mr, ovf = merge_insert(
            jnp.asarray(slab_k), jnp.asarray(slab_r),
            jnp.asarray(keys), jnp.asarray(rows),
        )
        rk, rr, rovf = merge_insert_ref(slab_k, slab_r, keys, rows,
                                        slab_k.shape[0])
        np.testing.assert_array_equal(np.asarray(mk), rk)
        np.testing.assert_array_equal(np.asarray(mr), rr)
        assert int(ovf) == rovf
        return int(ovf)

    def test_pad_only_rows(self):
        # an all-PAD route buffer (an update whose keys all went to other
        # shards): no pairs, no examined work, slab unchanged
        from repro.core.types import PAD_ID

        slab_k, slab_r = self._slab([(3, 0), (5, 1), (5, 2)], cap=16)
        keys = np.full((8,), PAD_KEY, np.int32)
        rows = np.full((8,), PAD_ID, np.int32)
        assert self._check_probe(slab_k, slab_r, keys, rows) == 0
        assert self._check_merge(slab_k, slab_r, keys, rows) == 0
        # and on a still-empty slab
        empty_k, empty_r = self._slab([], cap=16)
        assert self._check_probe(empty_k, empty_r, keys, rows) == 0

    def test_single_key_world(self):
        # every resident entry and every incoming row shares ONE key: the
        # bucket spans the whole slab, probe must emit old*new + C(new, 2)
        from repro.core.types import PAD_ID

        old = 6
        slab_k, slab_r = self._slab([(7, i) for i in range(old)], cap=16)
        new = 5
        keys = np.full((new,), 7, np.int32)
        rows = (old + np.arange(new)).astype(np.int32)
        examined = self._check_probe(slab_k, slab_r, keys, rows,
                                     nn_cap=32, no_cap=64)
        assert examined == old * new + new * (new - 1) // 2
        self._check_merge(slab_k, slab_r, keys, rows)

    def test_cap_boundary_insert_exactly_full(self):
        # merging into a slab that lands EXACTLY at capacity: no overflow,
        # no dropped entry, sorted invariant preserved
        cap = 8
        slab_k, slab_r = self._slab([(2, 0), (4, 1), (9, 2)], cap=cap)
        keys = np.asarray([1, 4, 4, 9, 11], np.int32)
        rows = np.asarray([10, 11, 12, 13, 14], np.int32)
        assert self._check_merge(slab_k, slab_r, keys, rows) == 0
        from repro.core.device_index import merge_insert

        mk, _, ovf = merge_insert(jnp.asarray(slab_k), jnp.asarray(slab_r),
                                  jnp.asarray(keys), jnp.asarray(rows))
        mk = np.asarray(mk)
        assert int(ovf) == 0
        assert (mk != PAD_KEY).sum() == cap  # exactly full
        assert (np.diff(mk) >= 0).all()      # still sorted

    def test_overflow_drop_accounting(self):
        # one entry too many: the drop is COUNTED (the engine regrows and
        # retries; a committed drop never happens), and the probe's pair
        # buffers report their own overflow the same way
        cap = 4
        slab_k, slab_r = self._slab([(2, 0), (4, 1), (9, 2)], cap=cap)
        keys = np.asarray([1, 4], np.int32)
        rows = np.asarray([10, 11], np.int32)
        assert self._check_merge(slab_k, slab_r, keys, rows) == 1
        from repro.core.device_index import probe_pairs

        # 5 incoming rows of one key against 3 residents of the same key:
        # 15 old-new + 10 new-new collisions vs caps (8, 8)
        slab_k, slab_r = self._slab([(7, 0), (7, 1), (7, 2)], cap=8)
        keys = np.full((5,), 7, np.int32)
        rows = (3 + np.arange(5)).astype(np.int32)
        lo, hi, examined, ovf = probe_pairs(
            jnp.asarray(slab_k), jnp.asarray(slab_r),
            jnp.asarray(keys), jnp.asarray(rows), nn_cap=8, no_cap=8,
        )
        assert int(examined) == 15 + 10      # exact even when overflowing
        assert int(ovf) == (10 - 8) + (15 - 8)

    def test_randomized_vs_reference(self):
        # seeded sweep over mixed shapes (the differential harness pins
        # the end-to-end join; this pins the kernels in isolation)
        from repro.core.types import PAD_ID

        rng = np.random.default_rng(0)
        for trial in range(10):
            cap = int(rng.integers(8, 40))
            n_old = int(rng.integers(0, cap // 2 + 1))
            ent = sorted(
                (int(k), i)
                for i, k in enumerate(rng.integers(0, 9, n_old))
            )
            slab_k, slab_r = self._slab(ent, cap=cap)
            r = int(rng.integers(1, 20))
            keys = rng.integers(0, 9, r).astype(np.int32)
            rows = (100 + np.arange(r)).astype(np.int32)
            drop = rng.random(r) < 0.3
            keys[drop] = PAD_KEY
            rows[drop] = PAD_ID
            self._check_probe(slab_k, slab_r, keys, rows,
                              nn_cap=256, no_cap=256)
            self._check_merge(slab_k, slab_r, keys, rows)


def _ref_scores(codes, lengths, left, right, betas, ta=None, tb=None,
                oa=None, ob=None, W=None):
    """Unchunked textbook-DP scores (``lcs_ref``) of table-indexed pairs."""
    from repro.core.similarity import (
        gather_windows, lcs_ref, mss_scores, multi_level_lcs,
    )

    if W is None:
        lvl = multi_level_lcs(codes[left], lengths[left], codes[right],
                              lengths[right], impl=lcs_ref)
    else:
        lvl = multi_level_lcs(
            gather_windows(codes[ta], oa, W),
            jnp.clip(lengths[ta] - oa, 0, W),
            gather_windows(codes[tb], ob, W),
            jnp.clip(lengths[tb] - ob, 0, W), impl=lcs_ref,
        )
    return lvl, mss_scores(lvl, betas)


class TestScoreChunks:
    """Chunked scoring (``similarity.score_indexed``) at the chunk
    boundaries, and table-indexed scoring with N + P past the 1 MiB SMEM
    bound that used to cap the fused kernel's scalar-prefetched tables:
    bit-identical to unchunked ``lcs_ref`` scores for every impl family."""

    N, H, L = 140_000, 3, 10

    @pytest.fixture(scope="class")
    def world(self):
        rng = np.random.default_rng(7)
        lengths = rng.integers(1, self.L + 1, size=self.N).astype(np.int32)
        codes = rng.integers(0, 4, size=(self.N, self.H, self.L))
        pad = np.arange(self.L)[None, None, :] >= lengths[:, None, None]
        codes = np.where(pad, -1, codes).astype(np.int32)
        betas = np.asarray([0.5, 0.25, 0.25], np.float32)
        return jnp.asarray(codes), jnp.asarray(lengths), jnp.asarray(betas)

    def _run(self, world, P, impl_name, windowed):
        import jax

        from repro.core.similarity import lcs_impl, score_indexed

        codes, lengths, betas = world
        rng = np.random.default_rng(P)
        left = jnp.asarray(rng.integers(0, self.N, size=P), jnp.int32)
        right = jnp.asarray(rng.integers(0, self.N, size=P), jnp.int32)
        kw, ref_kw = {}, {}
        if windowed:
            W = 4
            oa = jnp.asarray(rng.integers(0, self.L - W + 1, size=P),
                             jnp.int32)
            ob = jnp.asarray(rng.integers(0, self.L - W + 1, size=P),
                             jnp.int32)
            kw = dict(window=W, off_a=oa, off_b=ob)
            ref_kw = dict(ta=left, tb=right, oa=oa, ob=ob, W=W)
        fn = jax.jit(lambda c, n, l, r, b: score_indexed(
            c, n, c, n, l, r, b, impl=lcs_impl(impl_name), **kw))
        got = fn(codes, lengths, left, right, betas)
        want = _ref_scores(codes, lengths, left, right, betas, **ref_kw)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        return str(jax.make_jaxpr(fn)(codes, lengths, left, right, betas))

    @pytest.mark.parametrize("windowed", [False, True])
    @pytest.mark.parametrize(
        "impl_name", ["fused-interpret", "wavefront", "ref"]
    )
    @pytest.mark.parametrize("delta", [-1, 0, 1])
    def test_chunk_boundaries(self, world, monkeypatch, delta, impl_name,
                              windowed):
        from repro.core import compat
        from repro.core.similarity import score_chunk

        # no device memory to spare: score_chunk falls to its 1,024 floor
        monkeypatch.setattr(compat, "device_memory_bytes", lambda: 0)
        chunk = score_chunk(self.H, self.L, lane_dense=True)
        assert chunk == score_chunk(self.H, self.L, lane_dense=False) == 1024
        jaxpr = self._run(world, chunk + delta, impl_name, windowed)
        # the chunk loop slices chunk-sized pair windows exactly when P
        # exceeds one chunk
        assert (f"slice_sizes=({chunk},)" in jaxpr) == (delta > 0)

    @pytest.mark.parametrize("windowed", [False, True])
    def test_tables_past_old_smem_bound(self, world, windowed):
        # N + P = 143,000 > 131,072: the int32 tables the old kernel
        # prefetched into SMEM would not fit its 1 MiB
        self._run(world, 3_000, "fused-interpret", windowed)
