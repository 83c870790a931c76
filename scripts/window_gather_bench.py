#!/usr/bin/env python
"""Time candidate-window gathers on the chip.

The count program gathers, for each frontier row, the `width` entries of
the flat CSR array that start at the row's offset.  This script times
four ways to build that [cap, width] window at the bucket shapes the
benchmark's cells use, with offsets drawn from the real CSR rows of the
Graph500 graphs (bench/configs), and checks that each way returns the
scalar gather's window bit for bit:

  scalar   flat[start[:, None] + arange(width)]: cap x width scalar
           indices, one per element
  slice    (a) lax.gather with slice_sizes=(width,): one contiguous slice
           per row, at its unaligned start
  tiles    (b) gather the aligned 128-entry tiles that cover each row from
           the [F/128, 128] view of the array (one slice of nt tiles per
           row), then shift each row left by start % 128 with a barrel
           shifter of static lane shifts
  dma      (c) a Pallas kernel: each row's covering tiles are DMA'd into
           VMEM, rotated by start % 128 and written to the window
  rows     (c) with the output written as whole [rows, 128] tiles: a
           strided load takes tile k of every row, and a barrel shifter
           of static lane rotations moves each row by start % 128
  kernel   the count program's gather, `repro.kernels.ops.window_gather`
           (form `dma`, in column chunks)

    PYTHONPATH=src python scripts/window_gather_bench.py [--iters 30]

One JSON line per (shape, way) on stdout: median ms per gather over
`--iters` timed calls.  On a CPU it runs at a tiny size with the Pallas
interpreter (`--tiny`), to check the ways against each other; its
times mean nothing.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from repro.kernels import ops  # noqa: E402

TILE = 128
# (config, cap, width, lowest degree of the bucket): the three window
# shapes that take most of the cells' device time.  The last shape is a
# widest bucket, which takes every row above `lo`.
SHAPES = (("graph500-s13", 32768, 128, 0),
          ("graph500-s13", 6048, 1024, 128),
          ("graph500-s17", 777, 15715, 1024))
TINY = (("tiny", 64, 128, 0), ("tiny", 24, 256, 16), ("tiny", 8, 300, 64))


def scalar(flat, start, width):
    return flat[start[:, None] + jnp.arange(width, dtype=start.dtype)[None, :]]


def slice_gather(flat, start, width):
    dn = lax.GatherDimensionNumbers(offset_dims=(1,), collapsed_slice_dims=(),
                                    start_index_map=(0,))
    return lax.gather(flat, start[:, None], dn, slice_sizes=(width,),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def shift_rows(x, s, width):
    """x[r, s[r] : s[r] + width] for 0 <= s < TILE: one static lane shift
    per bit of s, highest first, each keeping only the columns the
    remaining bits can still reach."""
    for b in reversed(range(TILE.bit_length() - 1)):
        k = 1 << b
        keep = width + k - 1
        x = jnp.where(((s >> b) & 1)[:, None] == 1,
                      x[:, k:k + keep], x[:, :keep])
    return x


def tile_gather(flat2d, start, width):
    nt = -(-width // TILE) + 1
    dn = lax.GatherDimensionNumbers(offset_dims=(1, 2),
                                    collapsed_slice_dims=(),
                                    start_index_map=(0,))
    tiles = lax.gather(flat2d, (start // TILE)[:, None], dn,
                       slice_sizes=(nt, TILE),
                       mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    return shift_rows(tiles.reshape(start.shape[0], nt * TILE),
                      start % TILE, width)


def _dma_body(starts_sref, flat_ref, out_ref, buf_ref, rot_ref, sems, *,
              block_b, nt):
    i = pl.program_id(0)
    dmas = []
    for r in range(block_b):
        t0 = jnp.right_shift(starts_sref[i * block_b + r], 7)
        dma = pltpu.make_async_copy(flat_ref.at[pl.ds(t0, nt)],
                                    buf_ref.at[r], sems.at[r])
        dma.start()
        dmas.append(dma)
    for dma in dmas:
        dma.wait()
    lane = lax.broadcasted_iota(jnp.int32, (nt - 1, TILE), 1)
    for r in range(block_b):
        s = jnp.bitwise_and(starts_sref[i * block_b + r], TILE - 1)
        rot_ref[...] = pltpu.roll(buf_ref[r], jnp.bitwise_and(TILE - s,
                                                              TILE - 1), 1)
        out_ref[r] = jnp.where(lane < TILE - s, rot_ref[pl.ds(0, nt - 1), :],
                               rot_ref[pl.ds(1, nt - 1), :])


def dma_gather(flat2d, start, width, *, block_b=8, interpret=False):
    cap = start.shape[0]
    nt = -(-width // TILE) + 1
    pb = (-cap) % block_b
    starts = jnp.pad(start, (0, pb))
    B = cap + pb
    out = pl.pallas_call(
        functools.partial(_dma_body, block_b=block_b, nt=nt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // block_b,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_b, nt - 1, TILE),
                                   lambda i, ss: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((block_b, nt, TILE), jnp.int32),
                            pltpu.VMEM((nt, TILE), jnp.int32),
                            pltpu.SemaphoreType.DMA((block_b,))]),
        out_shape=jax.ShapeDtypeStruct((B, nt - 1, TILE), jnp.int32),
        interpret=interpret, name="window_dma",
    )(starts, flat2d)
    return out.reshape(B, (nt - 1) * TILE)[:cap, :width]


def _rows_body(starts_sref, flat_ref, out_ref, buf_ref, sems, *,
               block_b, nt):
    """Row r's nt covering tiles land at buf[r * nt:]; a strided load then
    takes tile k of every row as one [block_b, TILE] block, which a
    barrel shifter of static lane rotations moves left by each row's
    start % TILE."""
    i = pl.program_id(0)
    dmas = []
    for r in range(block_b):
        t0 = jnp.right_shift(starts_sref[i * block_b + r], 7)
        dma = pltpu.make_async_copy(flat_ref.at[pl.ds(t0, nt)],
                                    buf_ref.at[pl.ds(r * nt, nt)], sems.at[r])
        dma.start()
        dmas.append(dma)
    sub = lax.broadcasted_iota(jnp.int32, (block_b, 1), 0)
    s = jnp.zeros((block_b, 1), jnp.int32)
    for r in range(block_b):
        s = jnp.where(sub == r,
                      jnp.bitwise_and(starts_sref[i * block_b + r], TILE - 1),
                      s)
    lane = lax.broadcasted_iota(jnp.int32, (block_b, TILE), 1)
    for dma in dmas:
        dma.wait()

    def rot(x):
        for b in reversed(range(TILE.bit_length() - 1)):
            x = jnp.where(jnp.bitwise_and(jnp.right_shift(s, b), 1) == 1,
                          pltpu.roll(x, TILE - (1 << b), 1), x)
        return x

    def tile(k):
        return rot(buf_ref[pl.ds(k, block_b, stride=nt), :])

    def chunk(k, prev):
        nxt = tile(k + 1)
        out_ref[:, pl.ds(pl.multiple_of(k * TILE, TILE), TILE)] = jnp.where(
            lane < TILE - s, prev, nxt)
        return nxt

    lax.fori_loop(0, nt - 1, chunk, tile(0))


def rows_gather(flat2d, start, width, *, block_b=8, interpret=False):
    cap = start.shape[0]
    nt = -(-width // TILE) + 1
    pb = (-cap) % block_b
    starts = jnp.pad(start, (0, pb))
    B = cap + pb
    out = pl.pallas_call(
        functools.partial(_rows_body, block_b=block_b, nt=nt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // block_b,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_b, (nt - 1) * TILE),
                                   lambda i, ss: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block_b * nt, TILE), jnp.int32),
                            pltpu.SemaphoreType.DMA((block_b,))]),
        out_shape=jax.ShapeDtypeStruct((B, (nt - 1) * TILE), jnp.int32),
        interpret=interpret, name="window_rows",
    )(starts, flat2d)
    return out[:cap, :width]


def graph_rows(config: str):
    """(flat with sentinel pad and one spare tile, indptr, degrees)."""
    from graphgen import make_edges
    from repro.graph.csr import GraphCSR
    from repro.kernels.ops import flat_gather_pad

    if config == "tiny":
        rng = np.random.default_rng(0)
        n = 600
        edges = rng.integers(0, n, size=(6000, 2))
        edges[:, 1] = np.where(rng.random(6000) < 0.3, 0, edges[:, 1])
    else:
        with open(os.path.join(ROOT, "bench", "configs",
                               f"{config}.json")) as f:
            gen = json.load(f)["generator"]
        n, edges = make_edges(gen, 1)
    g = GraphCSR.from_edges(n, edges, relabel_by_degree=True)
    flat = np.concatenate([
        g.indices,
        np.full(flat_gather_pad(len(g.indices)) + TILE,
                np.iinfo(np.int32).max, dtype=np.int32)])
    return flat, g.indptr, g.degrees


def timed(fn, args, iters):
    out = fn(*args).block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny graph and shapes, Pallas interpreter (CPU)")
    args = ap.parse_args(argv)
    interpret = args.tiny
    shapes = TINY if args.tiny else SHAPES
    dev = jax.devices()[0]
    graphs = {}
    for config, cap, width, lo in shapes:
        if config not in graphs:
            graphs[config] = graph_rows(config)
        flat_np, indptr, deg = graphs[config]
        rows = np.flatnonzero((deg > lo) & ((deg <= width)
                                            | (width == shapes[-1][2])))
        rng = np.random.default_rng(cap)
        start_np = indptr[rng.choice(rows, cap)].astype(np.int32)
        flat = jnp.asarray(flat_np)
        flat2d = flat.reshape(-1, TILE)
        start = jnp.asarray(start_np)
        ways = {
            "scalar": (jax.jit(scalar, static_argnums=2), (flat, start, width)),
            "slice": (jax.jit(slice_gather, static_argnums=2),
                      (flat, start, width)),
            "tiles": (jax.jit(tile_gather, static_argnums=2),
                      (flat2d, start, width)),
        }
        for bb in (8, 32):
            ways[f"dma_b{bb}"] = (
                jax.jit(functools.partial(dma_gather, block_b=bb,
                                          interpret=interpret),
                        static_argnums=2), (flat2d, start, width))
        for bb in (8, 32, 64):
            ways[f"rows_b{bb}"] = (
                jax.jit(functools.partial(rows_gather, block_b=bb,
                                          interpret=interpret),
                        static_argnums=2), (flat2d, start, width))
        ways["kernel"] = (
            jax.jit(functools.partial(ops.window_gather, interpret=interpret),
                    static_argnames="width"), (flat, start))
        ref = None
        for name, (fn, fargs) in ways.items():
            if name == "kernel":
                fn = functools.partial(fn, width=width)
            try:
                out, ms = timed(fn, fargs, args.iters)
                out = np.asarray(out)
                if ref is None:
                    ref = out
                line = {"config": config, "cap": cap, "width": width,
                        "way": name, "ms": ms,
                        "equal": bool(np.array_equal(out, ref))}
            except Exception as e:  # noqa: BLE001 — report and go on
                line = {"config": config, "cap": cap, "width": width,
                        "way": name, "error": f"{type(e).__name__}: {e}"[:400]}
            line["device"] = dev.device_kind
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
