"""The candidate window gathered as row slices equals the scalar gather.

`candidate_window` reads each row's window flat[start : start + width]
as one contiguous slice (XLA slices on the portable path, the Pallas
row gather on the fused path, here in the interpreter).  Its answer has
to be the one the per-element gather flat[start + arange(width)] gives,
on every row: rows whose window runs into the sentinel pad, the last
real row, degree-0 rows and the sentinel row n.  That rests on the pad:
each offset leaves at least max-degree entries after it, which the
second half of the file checks for every array the gather reads.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.executor import candidate_window, device_graph
from repro.graph.csr import GraphCSR
from repro.kernels import ops
from repro.live import DeltaOverlay

HUB = 1100                       # the hub's degree: past the 1024 bucket


def _graph(labeled: bool) -> GraphCSR:
    """A hub of degree HUB (so width 1024 fits inside the pad), a sparse
    random remainder, and isolated vertices at the end of the id order."""
    rng = np.random.default_rng(5)
    n = HUB + 200
    hub = np.stack([np.zeros(HUB, np.int64), np.arange(1, HUB + 1)], axis=1)
    rest = rng.integers(1, n - 20, size=(3000, 2))
    edges = np.concatenate([hub, rest])
    labels = rng.integers(0, 3, size=n) if labeled else None
    return GraphCSR.from_edges(n, edges, relabel_by_degree=True,
                               labels=labels)


@pytest.fixture(scope="module", params=[False, True],
                ids=["unlabeled", "labeled"])
def graph(request):
    return _graph(request.param)


def _scalar_window(src, start, length, width):
    """The per-element gather the row gather replaced, on the host."""
    cand = src[start[:, None] + np.arange(width)[None, :]]
    ok = np.arange(width)[None, :] < length[:, None]
    return cand, ok


@pytest.mark.parametrize("pallas", ["0", "interpret"],
                         ids=["portable", "pallas"])
@pytest.mark.parametrize("width", [128, 1024, "W"])
def test_row_gather_equals_scalar_gather(graph, width, pallas):
    W = graph.max_degree
    width = W if width == "W" else width
    arrays = device_graph(graph)
    n = graph.n
    deg = np.asarray(graph.degrees)
    # the hub, rows of every degree, and the last rows (degree 0, their
    # windows deep in the pad) through the sentinel row n
    low = int(np.argmax(deg < 5))
    base = np.concatenate([np.arange(0, n, 53), [low],
                           np.arange(n - 24, n + 1)]).astype(np.int32)
    assert deg[-1] == 0 and deg[0] == W
    label = 1 if graph.labels is not None else None
    cand, ok = candidate_window(
        arrays.flat, arrays.indptr, arrays.degrees, jnp.asarray(base),
        width, labs=arrays.labs, label=label, pallas=pallas)
    if label is None:
        src = np.asarray(arrays.flat)
        start = np.asarray(arrays.indptr)[base]
        length = np.asarray(arrays.degrees)[base]
    else:
        src = np.asarray(arrays.labs[3])
        start = np.asarray(arrays.labs[1])[base, label]
        length = np.asarray(arrays.labs[2])[base, label]
    want_cand, want_ok = _scalar_window(src, start, length, width)
    assert cand.shape == (len(base), width)
    np.testing.assert_array_equal(np.asarray(ok), want_ok)
    np.testing.assert_array_equal(np.asarray(cand), want_cand)
    if label is None:
        # the hub, a low-degree row and the last (degree-0) row hold
        # exactly their neighbours
        for i in (0, list(base).index(low), len(base) - 2):
            row = np.asarray(cand)[i][np.asarray(ok)[i]]
            np.testing.assert_array_equal(
                row, graph.neighbors(base[i])[:width])


def _assert_windows_inside(src_len, starts, W):
    """Every offset leaves a whole max-degree window inside the array."""
    assert int(np.max(starts)) + W <= src_len


def test_sentinel_pad_covers_every_window(graph):
    nnz = int(graph.indptr[-1])
    W = graph.max_degree
    assert len(graph.indices) - nnz >= W
    arrays = device_graph(graph)
    flat_len = arrays.flat.shape[0]
    assert flat_len % ops.GATHER_TILE == 0
    _assert_windows_inside(flat_len, np.asarray(arrays.indptr), W)
    if graph.labels is not None:
        lv = graph.label_view
        assert len(lv.flat) - nnz >= W
        lab_flat = arrays.labs[3]
        assert lab_flat.shape[0] % ops.GATHER_TILE == 0
        _assert_windows_inside(lab_flat.shape[0], np.asarray(arrays.labs[1]),
                               W)
        # the label segments' lengths never exceed the full degree
        assert int(lv.lens.sum(axis=1).max()) == W


def test_live_view_keeps_a_window_past_every_row():
    base = _graph(False)
    live = DeltaOverlay(base, window_headroom=8)
    rng = np.random.default_rng(9)
    # inserts at the hub and the last vertices grow rows past the base
    # max degree and move them into the patch region
    pairs = [(0, int(v)) for v in rng.integers(HUB + 1, base.n, size=6)]
    pairs += [(base.n - 1, int(v)) for v in rng.integers(1, base.n - 1,
                                                          size=6)]
    assert live.apply("insert_edges", pairs) > 0
    view = live.view
    arrays = device_graph(view)
    _assert_windows_inside(arrays.flat.shape[0],
                           np.asarray(view.indptr)[:view.n], view.max_degree)


@pytest.mark.parametrize("width", [1024 + 1, 2 * 1024 + 128])
def test_row_gather_in_column_chunks(width, monkeypatch):
    """Windows wider than CHUNK_TILES tiles are gathered in column
    chunks, the last one shorter; each row still equals its slice, the
    ones that end at the array's last entry included."""
    from repro.kernels import window

    monkeypatch.setattr(window, "CHUNK_TILES", 8)
    rng = np.random.default_rng(width)
    flat = rng.integers(0, 1 << 30, 40 * ops.GATHER_TILE).astype(np.int32)
    rows = window.BLOCK_ROWS
    starts = np.concatenate([[0, len(flat) - width, len(flat) - width - 5],
                             rng.integers(0, len(flat) - width, rows - 3)])
    out = window.window_gather_pallas(
        jnp.asarray(flat).reshape(-1, ops.GATHER_TILE),
        jnp.asarray(starts, jnp.int32), width=width, interpret=True)
    got = np.asarray(out).reshape(rows, -1)[:, :width]
    np.testing.assert_array_equal(
        got, flat[starts[:, None] + np.arange(width)[None, :]])
