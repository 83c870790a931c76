"""Jitted public wrappers around the Pallas kernels.

Handle padding to block multiples and the padding-value contract
(cand -1 / nbr INT_MAX), so callers pass ragged-ish data freely.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .intersect import (
    CAND_PAD, NBR_PAD, intersect_count_pallas, level_expand_pallas,
    membership_pallas,
)
from .window import BLOCK_ROWS, window_gather_pallas

# Tile width of the kernel's neighbor gather: the flat CSR array is read
# as rows of GATHER_TILE int32s (one lane-row of a vreg), and every DMA
# starts on such a row.  device_graph pads the resident array to a
# multiple of it, so the [F/GATHER_TILE, GATHER_TILE] view is free.
GATHER_TILE = 128


def flat_gather_pad(flat_len: int, tile: int = GATHER_TILE) -> int:
    """Sentinel entries to append to a flat CSR array of `flat_len`
    entries so it splits into whole `tile`-wide gather tiles.

    Safety invariant (what bounds the reads): every row lies inside the
    UNPADDED array — starts[p, b] + lens[p, b] ≤ flat_len — as real CSR
    rows do.  The kernel reads only the aligned tiles that cover a row,
    so its furthest read ends at round_up(flat_len, tile): the tile
    holding the row's last entry."""
    return (-flat_len) % tile


def _pad_to(x: jax.Array, mult0: int, mult1: int, value) -> jax.Array:
    b = (-x.shape[0]) % mult0
    d = (-x.shape[1]) % mult1
    if b or d:
        x = jnp.pad(x, ((0, b), (0, d)), constant_values=value)
    return x


@partial(jax.jit, static_argnames=("block_b", "block_d", "block_l", "interpret"))
def sorted_membership(
    cand: jax.Array,
    nbr: jax.Array,
    cand_valid: jax.Array | None = None,
    nbr_len: jax.Array | None = None,
    *,
    block_b: int = 8,
    block_d: int = 128,
    block_l: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """mask[b, d] = cand[b, d] ∈ nbr[b, :nbr_len[b]] (rows sorted asc).

    cand_valid / nbr_len mask out ragged tails; padding never matches.
    """
    B, D = cand.shape
    cand = cand.astype(jnp.int32)
    nbr = nbr.astype(jnp.int32)
    if cand_valid is not None:
        cand = jnp.where(cand_valid, cand, CAND_PAD)
    if nbr_len is not None:
        pos = jnp.arange(nbr.shape[1], dtype=jnp.int32)[None, :]
        nbr = jnp.where(pos < nbr_len[:, None], nbr, NBR_PAD)
    cand_p = _pad_to(cand, block_b, block_d, CAND_PAD)
    nbr_p = _pad_to(nbr, block_b, block_l, NBR_PAD)
    out = membership_pallas(
        cand_p, nbr_p,
        block_b=block_b, block_d=block_d, block_l=block_l,
        interpret=interpret,
    )
    return out[:B, :D]


@partial(jax.jit, static_argnames=("block_b", "block_d", "block_l", "interpret"))
def intersect_count(
    cand: jax.Array,
    nbr: jax.Array,
    cand_valid: jax.Array | None = None,
    nbr_len: jax.Array | None = None,
    *,
    block_b: int = 8,
    block_d: int = 128,
    block_l: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """cnt[b] = |{d : cand[b,d] ∈ nbr[b,:]}| — fused count kernel.

    Contract: nbr rows strictly increasing on the valid prefix."""
    B, D = cand.shape
    cand = cand.astype(jnp.int32)
    nbr = nbr.astype(jnp.int32)
    if cand_valid is not None:
        cand = jnp.where(cand_valid, cand, CAND_PAD)
    if nbr_len is not None:
        pos = jnp.arange(nbr.shape[1], dtype=jnp.int32)[None, :]
        nbr = jnp.where(pos < nbr_len[:, None], nbr, NBR_PAD)
    cand_p = _pad_to(cand, block_b, block_d, CAND_PAD)
    nbr_p = _pad_to(nbr, block_b, block_l, NBR_PAD)
    out = intersect_count_pallas(
        cand_p, nbr_p,
        block_b=block_b, block_d=block_d, block_l=block_l,
        interpret=interpret,
    )
    return out[:B]


@partial(jax.jit, static_argnames=("dirs", "count", "neg_from", "block_b",
                                   "block_l", "interpret"))
def level_expand(
    cand: jax.Array,                      # [B, D] candidate window
    flat: jax.Array,                      # [F] flat CSR indices array
    starts: jax.Array,                    # [P, B] CSR row offsets
    lens: jax.Array,                      # [P, B] valid row lengths
    extra: jax.Array | None = None,       # [B, E] prefix-vertex values
    cand_valid: jax.Array | None = None,  # [B, D] bool
    *,
    dirs: tuple = (),
    count: bool = False,
    neg_from: int | None = None,
    block_b: int = 8,
    block_l: int = GATHER_TILE,
    interpret: bool = False,
) -> jax.Array:
    """One fused, self-feeding Pallas pass for a whole expansion level.

    mask[b, d] = cand_valid[b, d]
               ∧ (∀p: cand[b, d] ∈ flat[starts[p, b] : +lens[p, b]])
               ∧ (∀e: cand[b, d] <op dirs[e]> extra[b, e])
    with <op> ∈ {+1: >, -1: <, 0: !=}.
    `count=True` returns cnt[b] = Σ_d mask[b, d] (int32) instead; with
    `neg_from` set, columns ≥ neg_from subtract instead of add (the
    fused IEP prefix-correction tail — DESIGN.md §4).

    The predecessor neighborhoods are gathered INSIDE the kernel from
    `flat` in aligned `block_l`-wide tiles (scalar-prefetched offsets,
    per-row DMA) — no caller ever materializes a stacked [P, B, W]
    window array.  Rows with no valid candidate get length 0, so the
    kernel fetches nothing for them.

    Contracts:
      * rows flat[starts[p,b] : +lens[p,b]] STRICTLY increasing (CSR
        neighborhoods are) — the per-candidate hit accumulator relies on
        at most one match per predecessor row;
      * every row lies inside the array: starts[p, b] + lens[p, b] ≤
        len(flat) (CSR rows do);
      * a `flat` whose length is not a multiple of block_l is padded
        here with flat_gather_pad() sentinels — device_graph pads the
        resident graph once, so the hot path never copies it.
    `interpret=True` runs the Pallas interpreter (CPU tests); the
    default lowers to a Mosaic TPU kernel.
    """
    B, D = cand.shape
    cand = cand.astype(jnp.int32)
    if cand_valid is not None:
        cand = jnp.where(cand_valid, cand, CAND_PAD)
        # a row with no candidate left needs no neighbor tile at all
        lens = jnp.where(cand_valid.any(axis=1)[None, :], lens, 0)
    cand_p = _pad_to(cand, block_b, block_l, CAND_PAD)
    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    pb = (-B) % block_b
    if pb:
        # padded rows: offset 0 / length 0 — the kernel fetches nothing
        starts = jnp.pad(starts, ((0, 0), (0, pb)))
        lens = jnp.pad(lens, ((0, 0), (0, pb)))
    flat = flat.astype(jnp.int32)
    pad = flat_gather_pad(flat.shape[0], block_l)
    if pad:
        flat = jnp.concatenate([flat, jnp.full(pad, NBR_PAD, jnp.int32)])
    if dirs:
        extra = extra.astype(jnp.int32)
        if pb:
            extra = jnp.pad(extra, ((0, pb), (0, 0)))
    out = level_expand_pallas(
        cand_p, flat.reshape(-1, block_l), starts, lens,
        extra if dirs else None,
        dirs=tuple(dirs), count=count, neg_from=neg_from,
        block_b=block_b, interpret=interpret,
    )
    return out[:B] if count else out[:B, :D]


@partial(jax.jit, static_argnames=("width", "interpret"))
def window_gather(
    flat: jax.Array,                      # [F] flat CSR indices array
    starts: jax.Array,                    # [B] window offsets
    *,
    width: int,
    interpret: bool = False,
) -> jax.Array:
    """win[b, :] = flat[starts[b] : starts[b] + width] as [B, width]
    int32, each row read as the aligned GATHER_TILE-wide tiles that
    cover it (one DMA per row) instead of `width` scalar indices.

    Contract: starts[b] + width ≤ len(flat) for every row — the windows
    of a sentinel-padded CSR array (`GraphCSR.from_edges` pads `indices`
    with max-degree sentinels).  Nothing past a row's window is read, so
    no window is clamped or shifted.  A `flat` whose length is not a
    multiple of GATHER_TILE is padded here (device_graph pads the
    resident arrays once)."""
    (B,) = starts.shape
    starts = starts.astype(jnp.int32)
    pb = (-B) % BLOCK_ROWS
    if pb:
        # padded rows read the window at 0, inside the array as well
        starts = jnp.pad(starts, (0, pb))
    flat = flat.astype(jnp.int32)
    pad = flat_gather_pad(flat.shape[0])
    if pad:
        flat = jnp.concatenate([flat, jnp.full(pad, NBR_PAD, jnp.int32)])
    out = window_gather_pallas(
        flat.reshape(-1, GATHER_TILE), starts, width=width,
        interpret=interpret)
    return out.reshape(B + pb, -1)[:B, :width]


# ------------------------------------------------------------ attention ---
@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "interpret"))
def flash_attention(
    q: jax.Array,                 # [B, Sq, H, hd]
    k: jax.Array,                 # [B, Sk, K, hd]
    v: jax.Array,                 # [B, Sk, K, hd]
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Model-layout wrapper: folds (B, heads) into the kernel's row dim,
    using the zero-copy GQA block-index mapping (kv heads are never
    materialized per q-head).  Falls back to shapes the kernel supports;
    callers guard on S % block == 0."""
    from .flash_attention import flash_attention_pallas

    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    # [B, S, H, hd] -> [B*H, S, hd] with q-heads of one kv-group adjacent,
    # so kernel row i maps to kv row i // G.
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    of = flash_attention_pallas(
        qf, kf, vf, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return of.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
