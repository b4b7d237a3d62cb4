"""The paper's headline claims, as tests:

* AnotherMe == centralized ground truth: QA1 = QA2 = 100%  (Figs. 10/12)
* the UDF implementation is logic-identical                 (section V.1)
* MinHash / BRP lose accuracy                               (Figs. 10/12)
* SSH completeness: every pair with MSS > rho shares a k-shingle for
  k <= floor(rho)+1                                         (section IV.3)
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AnotherMeConfig, centralized_similar_pairs, default_betas, encode_batch,
    forest_tables, maximal_cliques, minhash_candidates, qa1, qa2,
    run_anotherme, type_codes, udf_pipeline, brp_candidates,
)
from repro.core.shingling import shingles_from_types
from repro.core.similarity import multi_level_lcs
from repro.core.types import PAD_KEY
from repro.data import synthetic_setup


@pytest.fixture(scope="module")
def small_world():
    batch, forest = synthetic_setup(
        250, num_types=10, classes_per_type=5, num_places=200, seed=7
    )
    enc = encode_batch(batch, forest_tables(forest))
    cl, cr, _ = centralized_similar_pairs(enc, rho=2.0)
    cen_pairs = {(int(a), int(b)) for a, b in zip(cl, cr)}
    cen_comms = maximal_cliques(cen_pairs)
    return batch, forest, enc, cen_pairs, cen_comms


def test_anotherme_100_percent_accuracy(small_world):
    batch, forest, enc, cen_pairs, cen_comms = small_world
    res = run_anotherme(batch, forest, AnotherMeConfig())
    assert qa2(res.similar_pairs, cen_pairs) == 1.0
    assert res.similar_pairs == cen_pairs          # not just recall: exact
    assert qa1(res.communities, cen_comms) == 1.0
    assert res.communities == cen_comms


def test_udf_identical_logic(small_world):
    batch, forest, enc, cen_pairs, _ = small_world
    similar_udf, scores = udf_pipeline(
        np.asarray(batch.places), np.asarray(batch.lengths), forest
    )
    assert similar_udf == cen_pairs


def test_minhash_loses_accuracy(small_world):
    batch, forest, enc, cen_pairs, cen_comms = small_world
    res = run_anotherme(
        batch, forest, AnotherMeConfig(),
        candidate_fn=lambda e, b: minhash_candidates(
            type_codes(e), b.lengths, num_perm=16, bands=4,
            pair_capacity=1 << 18,
        ),
    )
    acc = qa2(res.similar_pairs, cen_pairs)
    assert acc < 0.9  # the paper reports large drops; exact value is data-dependent


def test_brp_worst_accuracy(small_world):
    batch, forest, enc, cen_pairs, cen_comms = small_world
    res_brp = run_anotherme(
        batch, forest, AnotherMeConfig(),
        candidate_fn=lambda e, b: brp_candidates(
            type_codes(e), b.lengths, num_types=forest.num_types,
            pair_capacity=1 << 18,
        ),
    )
    res_mh = run_anotherme(
        batch, forest, AnotherMeConfig(),
        candidate_fn=lambda e, b: minhash_candidates(
            type_codes(e), b.lengths, num_perm=16, bands=4,
            pair_capacity=1 << 18,
        ),
    )
    assert qa2(res_brp.similar_pairs, cen_pairs) <= qa2(res_mh.similar_pairs, cen_pairs)


def test_kernel_backed_pipeline_identical(small_world):
    batch, forest, enc, cen_pairs, _ = small_world
    res = run_anotherme(
        batch, forest, AnotherMeConfig(lcs_impl="fused-interpret")
    )
    assert res.similar_pairs == cen_pairs


@pytest.mark.parametrize("seed", range(30))
def test_ssh_completeness_theorem(seed):
    """Section IV.3: for threshold rho with n = floor(rho), any pair with
    MSS > rho has |M_typ| >= n+1, hence shares a (n+1)-sequential shingle.
    With k = 3 and rho = 2 every similar pair is SSH-recoverable."""
    rng = np.random.default_rng(seed)
    L, Q = 8, 6
    la, lb = rng.integers(3, L + 1, size=2)
    ta = rng.integers(0, Q, size=(1, L)).astype(np.int32)
    tb = rng.integers(0, Q, size=(1, L)).astype(np.int32)
    # single-level (type) world: betas = [1.0]
    lv = multi_level_lcs(
        jnp.asarray(ta[:, None, :]), jnp.asarray([la]),
        jnp.asarray(tb[:, None, :]), jnp.asarray([lb]),
    )
    mss = float(lv[0, 0])
    rho, k = 2.0, 3
    if mss > rho:
        ka = shingles_from_types(jnp.asarray(ta), jnp.asarray([la]), k=k, num_types=Q)
        kb = shingles_from_types(jnp.asarray(tb), jnp.asarray([lb]), k=k, num_types=Q)
        sa = set(np.asarray(ka)[0][np.asarray(ka)[0] != PAD_KEY].tolist())
        sb = set(np.asarray(kb)[0][np.asarray(kb)[0] != PAD_KEY].tolist())
        assert sa & sb, "similar pair missed by SSH — completeness violated"


def test_semantic_levels_2_to_6():
    """Fig. 15: accuracy stays 100% for 2..6-level hierarchies."""
    for n_levels in (2, 3, 4, 5, 6):
        batch, forest = synthetic_setup(
            120, num_types=8, classes_per_type=4, num_places=100,
            n_levels=n_levels, seed=11,
        )
        enc = encode_batch(batch, forest_tables(forest))
        cl, cr, _ = centralized_similar_pairs(enc, rho=2.0)
        cen_pairs = {(int(a), int(b)) for a, b in zip(cl, cr)}
        res = run_anotherme(batch, forest, AnotherMeConfig())
        assert res.similar_pairs == cen_pairs, f"n_levels={n_levels}"
