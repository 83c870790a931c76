"""Pallas TPU kernel for the candidate-window gather.

Each frontier row's candidates are `width` consecutive entries of the
flat CSR array, flat[start : start + width].  Gathered as cap × width
scalar indices that costs one gather per element; here each row is
read as the aligned `tile`-wide rows of the [F/tile, tile] view of the
array that cover it, with one DMA per row (the fused level kernel reads
its predecessor rows the same way, DESIGN.md §4), and rotated into
place in VMEM.

Read bound: a row's DMAs cover only the tiles that hold
flat[start : start + width], so every read lies inside an array that
holds start + width entries, rounded up to whole tiles.  The caller
keeps start + width ≤ len(flat) for every row, real or sentinel: the
CSR build pads `indices` with at least max-degree sentinels past every
row (`graph/csr.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Output tiles one grid step writes per row (a multiple of 8): windows
# wider than this many tiles are gathered in column chunks, so VMEM
# holds block_b rows of at most CHUNK_TILES + 1 tiles whatever the
# graph's max degree.
CHUNK_TILES = 128
# Rows one grid step gathers: at 32 this kernel was within 1.15x of
# the fastest row gather tried at every bucket shape of the benchmark's
# cells on a v5e, and 8 rows were up to 1.8x slower (PERF.md §6).
BLOCK_ROWS = 32


def _window_gather_body(starts_sref, flat_ref, out_ref, buf_ref, rot_ref,
                        sems, *, block_b: int, width: int, n_chunks: int):
    """Rows i·block_b.. of the window, output tiles j·chunk...

    Output tile k of a row is the tail of source tile k from lane
    start % tile on, followed by the head of source tile k + 1.  Row r's
    source tiles for this chunk land in buf[r]: `n_head` of them always,
    one more when the window reaches into it (the last chunk may hold
    fewer than `chunk` tiles, so its DMA is sized apart).  Each row's
    tiles are rotated left by start % tile with one lane rotation, and
    every output tile selects between rotated tiles k and k + 1.  Tiles
    past the window's end are never fetched: what the rows hold there
    is unspecified, and the wrapper crops it.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    tile = flat_ref.shape[1]
    shift = tile.bit_length() - 1
    chunk = out_ref.shape[1]
    n_out = -(-width // tile)
    starts = [starts_sref[i * block_b + r] for r in range(block_b)]

    def fetch(n_head):
        dmas = []
        for r in range(block_b):
            t = jnp.right_shift(starts[r], shift) + j * chunk
            off = jnp.bitwise_and(starts[r], tile - 1)
            head = pltpu.make_async_copy(
                flat_ref.at[pl.ds(t, n_head)],
                buf_ref.at[r, pl.ds(0, n_head)], sems.at[0, r])
            nxt = pltpu.make_async_copy(
                flat_ref.at[pl.ds(t + n_head, 1)],
                buf_ref.at[r, pl.ds(n_head, 1)], sems.at[1, r])
            reaches = off + width > (j * chunk + n_head) * tile
            head.start()
            pl.when(reaches)(nxt.start)
            dmas.append((head, nxt, reaches))
        for head, nxt, reaches in dmas:
            head.wait()
            pl.when(reaches)(nxt.wait)

    last = n_out - (n_chunks - 1) * chunk
    if last == chunk:
        fetch(chunk)
    else:
        pl.when(j < n_chunks - 1)(lambda: fetch(chunk))
        pl.when(j == n_chunks - 1)(lambda: fetch(last))

    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, tile), 1)
    for r in range(block_b):
        off = jnp.bitwise_and(starts[r], tile - 1)
        rot_ref[...] = pltpu.roll(buf_ref[r],
                                  jnp.bitwise_and(tile - off, tile - 1), 1)
        out_ref[r] = jnp.where(lane < tile - off,
                               rot_ref[pl.ds(0, chunk), :],
                               rot_ref[pl.ds(1, chunk), :])


def window_gather_pallas(
    flat: jax.Array,                      # [F/tile, tile] int32
    starts: jax.Array,                    # [B] int32, B % BLOCK_ROWS == 0
    *,
    width: int,
    interpret: bool = False,
) -> jax.Array:
    """[B, n_tiles, tile] int32 whose row b, read as one run of
    n_tiles · tile entries, begins with
    flat[starts[b] : starts[b] + width] (the flat array read through its
    tile view); entries from `width` on are unspecified."""
    (B,) = starts.shape
    tile = flat.shape[1]
    block_b = BLOCK_ROWS
    assert tile & (tile - 1) == 0 and B % block_b == 0, (flat.shape, B)
    n_out = -(-width // tile)
    # one chunk of the whole window, or whole chunks of CHUNK_TILES (a
    # block's tile count must then be a multiple of 8) and a shorter last
    chunk = n_out if n_out <= CHUNK_TILES else CHUNK_TILES
    n_chunks = -(-n_out // chunk)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_window_gather_body, block_b=block_b,
                              width=width, n_chunks=n_chunks),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B // block_b, n_chunks),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((block_b, chunk, tile),
                                       lambda i, j, ss: (i, j, 0)),
                scratch_shapes=[
                    pltpu.VMEM((block_b, chunk + 1, tile), jnp.int32),
                    pltpu.VMEM((chunk + 1, tile), jnp.int32),
                    pltpu.SemaphoreType.DMA((2, block_b)),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, n_chunks * chunk, tile),
                                           jnp.int32),
            interpret=interpret, name="window_gather",
        )(starts, flat)
    return out
