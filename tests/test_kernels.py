"""Per-kernel validation: sweep shapes/dtypes, assert against ref.py.

Kernels run in the Pallas interpreter (interpret=True) on the CPU; the
BlockSpec tiling and grid logic are identical to the hardware path.
"""
from functools import partial

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import (
    intersect_count_ref, membership_ref, membership_ref_searchsorted,
)

sorted_membership = partial(ops.sorted_membership, interpret=True)
intersect_count = partial(ops.intersect_count, interpret=True)


def _mk(rng, B, D, L, dtype, hi=2000):
    # strictly increasing rows (CSR contract): sample without replacement
    nbr = np.stack(
        [np.sort(rng.choice(hi, size=L, replace=False)) for _ in range(B)]
    ).astype(dtype)
    cand = rng.integers(0, hi, size=(B, D)).astype(dtype)
    return cand, nbr


SHAPES = [
    (1, 1, 1),
    (3, 5, 7),          # nothing aligned
    (8, 128, 128),      # exactly one block
    (16, 256, 384),     # multiple blocks each dim
    (9, 130, 200),      # ragged over block boundaries
    (2, 300, 64),       # D > L
    (32, 64, 512),      # L > D
]


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", [np.int32, np.int16], ids=["i32", "i16"])
def test_membership_matches_ref(shape, dtype):
    B, D, L = shape
    rng = np.random.default_rng(B * 1000 + D + L)
    cand, nbr = _mk(rng, B, D, L, dtype, hi=max(2048, L + 1))
    got = sorted_membership(jnp.asarray(cand), jnp.asarray(nbr))
    want = membership_ref(jnp.asarray(cand), jnp.asarray(nbr))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_intersect_count_matches_ref(shape):
    B, D, L = shape
    rng = np.random.default_rng(B + D * 31 + L * 7)
    cand, nbr = _mk(rng, B, D, L, np.int32, hi=max(4096, L + 1))
    got = intersect_count(jnp.asarray(cand), jnp.asarray(nbr))
    want = membership_ref(jnp.asarray(cand), jnp.asarray(nbr)).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ragged_masks():
    rng = np.random.default_rng(0)
    B, D, L = 6, 100, 150
    cand, nbr = _mk(rng, B, D, L, np.int32)
    nbr_len = rng.integers(0, L + 1, size=B).astype(np.int32)
    cand_valid = rng.random((B, D)) < 0.7
    got = sorted_membership(
        jnp.asarray(cand), jnp.asarray(nbr),
        jnp.asarray(cand_valid), jnp.asarray(nbr_len),
    )
    want = np.zeros((B, D), dtype=bool)
    for b in range(B):
        valid_nbrs = set(nbr[b, : nbr_len[b]].tolist())
        for d in range(D):
            want[b, d] = cand_valid[b, d] and cand[b, d] in valid_nbrs
    np.testing.assert_array_equal(np.asarray(got), want)
    cnt = intersect_count(
        jnp.asarray(cand), jnp.asarray(nbr),
        jnp.asarray(cand_valid), jnp.asarray(nbr_len),
    )
    np.testing.assert_array_equal(np.asarray(cnt), want.sum(axis=1))


def test_two_oracles_agree():
    rng = np.random.default_rng(3)
    cand, nbr = _mk(rng, 8, 64, 64, np.int32)
    a = membership_ref(jnp.asarray(cand), jnp.asarray(nbr))
    b = membership_ref_searchsorted(jnp.asarray(cand), jnp.asarray(nbr))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("blocks", [(8, 128, 128), (8, 128, 256), (16, 256, 128)])
def test_block_shape_invariance(blocks):
    """Different BlockSpec tilings must give identical results."""
    bb, bd, bl = blocks
    rng = np.random.default_rng(9)
    cand, nbr = _mk(rng, 12, 200, 300, np.int32)
    got = sorted_membership(
        jnp.asarray(cand), jnp.asarray(nbr),
        block_b=bb, block_d=bd, block_l=bl,
    )
    want = membership_ref(jnp.asarray(cand), jnp.asarray(nbr))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_duplicate_candidates_counted_separately():
    cand = jnp.asarray([[5, 5, 5, 7]], dtype=jnp.int32)
    nbr = jnp.asarray([[1, 5, 9, 2**31 - 1]], dtype=jnp.int32)
    assert int(intersect_count(cand, nbr)[0]) == 3
