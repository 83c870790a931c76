"""Pallas intersection-kernel micro-benchmark (the paper's hot spot).

Compares three implementations of batched membership/intersection-count
over sorted padded neighbor lists:
  binary-search : the portable executor path (vectorized per-segment
                  binary search over flat CSR),
  pallas        : blocked broadcast-compare kernel (Pallas interpreter
                  on CPU — correctness only; Mosaic on a TPU),
  jnp-ref       : the pure-jnp oracle (ref.py).

On CPU only relative correctness + rough timing are meaningful; the
VMEM/roofline arguments for the kernel live in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from ._util import Row, emit

# The kernels lower to Mosaic on a TPU and run in the Pallas
# interpreter on any other backend; a CPU row is a correctness check,
# not a device time.
INTERPRET = jax.default_backend() != "tpu"

SHAPES_QUICK = [(256, 128, 128), (512, 128, 256)]
SHAPES_FULL = [(256, 128, 128), (512, 128, 256), (1024, 256, 512),
               (4096, 128, 128)]


def _data(B, D, L, seed=0):
    rng = np.random.default_rng(seed)
    # strictly increasing rows (CSR contract)
    nbr = np.stack(
        [np.sort(rng.choice(10 * L, size=L, replace=False)) for _ in range(B)]
    ).astype(np.int32)
    cand = rng.integers(0, 10 * L, size=(B, D)).astype(np.int32)
    return jnp.asarray(cand), jnp.asarray(nbr)


def _time(fn, *args, repeats=3):
    jax.block_until_ready(fn(*args))        # compile + warm
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run(full: bool = False) -> list[Row]:
    rows: list[Row] = []
    jref = jax.jit(ref.membership_ref)
    for (B, D, L) in (SHAPES_FULL if full else SHAPES_QUICK):
        cand, nbr = _data(B, D, L)
        out_ref = jref(cand, nbr)
        out_pl = ops.sorted_membership(cand, nbr, interpret=INTERPRET)
        assert bool(jnp.all(out_ref == out_pl)), (B, D, L)

        t_pl = _time(lambda: ops.sorted_membership(cand, nbr,
                                                   interpret=INTERPRET))
        t_ref = _time(lambda: jref(cand, nbr))
        cnt_pl = ops.intersect_count(cand, nbr, interpret=INTERPRET)
        assert bool(jnp.all(cnt_pl == out_ref.sum(axis=1)))
        t_cnt = _time(lambda: ops.intersect_count(cand, nbr,
                                                  interpret=INTERPRET))

        compares = B * D * L
        rows.append(Row("kernel", {"B": B, "D": D, "L": L,
                                   "impl": "pallas-membership"},
                        t_pl, "s", {"gcmp_per_s": compares / t_pl / 1e9}))
        rows.append(Row("kernel", {"B": B, "D": D, "L": L,
                                   "impl": "jnp-ref-membership"},
                        t_ref, "s", {"gcmp_per_s": compares / t_ref / 1e9}))
        rows.append(Row("kernel", {"B": B, "D": D, "L": L,
                                   "impl": "pallas-count"},
                        t_cnt, "s", {"gcmp_per_s": compares / t_cnt / 1e9}))
    return rows


LEVEL_SHAPES_QUICK = [(256, 128, 3, 128)]
LEVEL_SHAPES_FULL = [(256, 128, 3, 128), (512, 128, 4, 256),
                     (1024, 256, 2, 512)]


def _level_data(B, D, P, L, E=2, seed=0):
    """CSR-layout level data: one flat pool of strictly-increasing rows
    plus per-predecessor (starts, lens) — the layout the self-feeding
    kernel prefetches.  Nothing here (or anywhere) materializes the old
    [P, B, L] stacked window array."""
    rng = np.random.default_rng(seed)
    lens = np.full((P, B), L, np.int32)
    starts = (np.arange(P * B, dtype=np.int32) * L).reshape(P, B)
    flat = np.concatenate([
        np.sort(rng.choice(10 * L, size=L, replace=False)).astype(np.int32)
        for _ in range(P * B)
    ])
    cand = rng.integers(0, 10 * L, size=(B, D)).astype(np.int32)
    extra = rng.integers(0, 10 * L, size=(B, E)).astype(np.int32)
    dirs = tuple(1 if e % 2 == 0 else 0 for e in range(E))
    return (jnp.asarray(cand), jnp.asarray(flat), jnp.asarray(starts),
            jnp.asarray(lens), jnp.asarray(extra), dirs)


def _hbm_mb(*arrays) -> float:
    return sum(a.size * a.dtype.itemsize for a in arrays) / 2**20


def run_level(full: bool = False) -> list[Row]:
    """Self-feeding fused level expansion vs the per-predecessor
    composition, with the operand-HBM-peak accounting for DESIGN.md §4.

    per-pred      one `sorted_membership` pallas_call per predecessor
                  (window gathered host-side, ONE [B, L] array live at a
                  time) plus one XLA mask pass per restriction /
                  injectivity constraint — P + E separate sweeps.
    fused-gather  the whole level in ONE kernel pass; the predecessor
                  windows are DMA'd from the flat CSR array inside the
                  grid, so the only operands resident in HBM are the
                  graph itself + the candidate matrix.
    stacked (retired, PR 1..2): fused but fed a host-stacked [P, B, L]
                  window array — its ~P× operand peak is reported as
                  `hbm_peak_mb_stacked` for the before/after table; the
                  path itself no longer exists in the code base.
    """
    rows: list[Row] = []
    for (B, D, P, L) in (LEVEL_SHAPES_FULL if full else LEVEL_SHAPES_QUICK):
        cand, flat, starts, lens, extra, dirs = _level_data(B, D, P, L)
        E = len(dirs)

        @jax.jit
        def per_pred(cand, flat, starts, lens, extra):
            # the pre-fusion executor path: gather one predecessor's
            # window host-side, one membership kernel pass per
            # predecessor, then one XLA mask per comparison
            mask = jnp.ones(cand.shape, dtype=bool)
            for p in range(P):
                window = flat[starts[p][:, None]
                              + jnp.arange(L, dtype=jnp.int32)[None, :]]
                mask &= ops.sorted_membership(cand, window,
                                              nbr_len=lens[p],
                                              interpret=INTERPRET)
            for e, d in enumerate(dirs):
                ev = extra[:, e][:, None]
                mask &= (cand > ev) if d > 0 else (cand != ev)
            return mask

        fused = lambda: ops.level_expand(cand, flat, starts, lens, extra,
                                         dirs=dirs, interpret=INTERPRET)
        out_old = per_pred(cand, flat, starts, lens, extra)
        out_new = fused()
        assert bool(jnp.all(out_old == out_new)), (B, D, P, L)
        cnt = ops.level_expand(cand, flat, starts, lens, extra,
                               dirs=dirs, count=True, interpret=INTERPRET)
        assert bool(jnp.all(cnt == out_old.sum(axis=1))), (B, D, P, L)

        t_old = _time(lambda: per_pred(cand, flat, starts, lens, extra))
        t_new = _time(fused)
        t_cnt = _time(lambda: ops.level_expand(cand, flat, starts, lens,
                                               extra, dirs=dirs, count=True,
                                               interpret=INTERPRET))
        compares = B * D * L * P
        # operand HBM peaks (MB): what must be live at once to feed the
        # kernel, beyond the resident CSR itself
        peak_gather = _hbm_mb(cand, starts, lens, extra)
        peak_perpred = _hbm_mb(cand, extra) + B * L * 4 / 2**20
        peak_stacked = _hbm_mb(cand, extra) + P * B * L * 4 / 2**20
        keys = {"B": B, "D": D, "P": P, "L": L}
        rows.append(Row("level_expand", {**keys, "impl": "per-pred"},
                        t_old, "s", {"passes": P + E,
                                     "hbm_peak_mb": peak_perpred,
                                     "gcmp_per_s": compares / t_old / 1e9}))
        rows.append(Row("level_expand", {**keys, "impl": "fused-gather"},
                        t_new, "s", {"passes": 1,
                                     "hbm_peak_mb": peak_gather,
                                     "hbm_peak_mb_stacked": peak_stacked,
                                     "gcmp_per_s": compares / t_new / 1e9}))
        rows.append(Row("level_expand", {**keys,
                                         "impl": "fused-gather-count"},
                        t_cnt, "s", {"passes": 1,
                                     "hbm_peak_mb": peak_gather,
                                     "gcmp_per_s": compares / t_cnt / 1e9}))
    return rows


def main(full: bool = False):
    emit(run(full) + run_level(full), "kernel_intersect")


if __name__ == "__main__":
    import sys

    main("--full" in sys.argv)
