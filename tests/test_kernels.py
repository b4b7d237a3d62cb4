"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs ref.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import PAD_KEY


class TestLCS:
    @pytest.mark.parametrize("L", [8, 10, 16, 32])
    @pytest.mark.parametrize("B", [256, 600])
    def test_sweep(self, L, B):
        from repro.kernels.lcs.ops import lcs
        from repro.kernels.lcs.ref import lcs as ref

        rng = np.random.default_rng(L * 1000 + B)
        la = rng.integers(1, L + 1, size=B)
        lb = rng.integers(1, L + 1, size=B)
        a = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        b = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        a[np.arange(L)[None, :] >= la[:, None]] = -1
        b[np.arange(L)[None, :] >= lb[:, None]] = -2
        got = np.asarray(lcs(jnp.asarray(a), jnp.asarray(b)))
        want = np.asarray(ref(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_array_equal(got, want)

    def test_raw_pallas_path(self):
        from repro.kernels.lcs.kernel import lcs_pallas
        from repro.kernels.lcs.ref import lcs as ref

        rng = np.random.default_rng(0)
        B, L = 512, 16
        a = rng.integers(0, 4, size=(B, L)).astype(np.int32)
        b = rng.integers(0, 4, size=(B, L)).astype(np.int32)
        got = np.asarray(
            lcs_pallas(jnp.asarray(a), jnp.asarray(b), interpret=True)
        )
        want = np.asarray(ref(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_array_equal(got, want)


class TestShingle:
    @pytest.mark.parametrize("L,k,Q", [(10, 3, 30), (16, 3, 300), (12, 4, 30), (8, 2, 10)])
    def test_sweep(self, L, k, Q):
        from repro.core.shingling import shingles_from_types
        from repro.kernels.shingle.ops import shingle_keys

        rng = np.random.default_rng(k * 7 + Q)
        N = 300
        lengths = rng.integers(k, L + 1, size=N).astype(np.int32)
        types = rng.integers(0, Q, size=(N, L)).astype(np.int32)
        got = np.asarray(
            shingle_keys(jnp.asarray(types), jnp.asarray(lengths), k=k, num_types=Q)
        )
        want = np.asarray(
            shingles_from_types(jnp.asarray(types), jnp.asarray(lengths), k=k, num_types=Q)
        )
        for i in range(N):
            g = set(got[i][got[i] != PAD_KEY].tolist())
            w = set(want[i][want[i] != PAD_KEY].tolist())
            assert g == w, i


class TestMinhash:
    @pytest.mark.parametrize("L,Q,P", [(10, 30, 16), (16, 300, 32), (12, 10, 8)])
    def test_sweep(self, L, Q, P):
        from repro.kernels.minhash.ops import minhash_signatures as kern
        from repro.kernels.minhash.ref import minhash_signatures as ref

        rng = np.random.default_rng(L + Q + P)
        N = 513
        lengths = rng.integers(1, L + 1, size=N).astype(np.int32)
        types = rng.integers(0, Q, size=(N, L)).astype(np.int32)
        got = np.asarray(kern(jnp.asarray(types), jnp.asarray(lengths),
                              num_perm=P, block_b=256))
        want = np.asarray(ref(jnp.asarray(types), jnp.asarray(lengths), num_perm=P))
        np.testing.assert_array_equal(got, want)
