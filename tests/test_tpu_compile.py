"""Compile rehearsals for a described TPU v5e: what the chip's compiler
refuses is caught here, with no chip attached.

The topology is described inside a module fixture (never at import:
only one process at a time may load the TPU compiler's library, and
every xdist worker imports every test file).  The cases share one xdist
group, so `--dist loadgroup` (like `loadfile`) keeps them on the one
worker that loads the library.  Where libtpu is installed the rehearsal
must run: a topology that cannot be described there fails the cases
instead of skipping them.  Nothing here runs on a device;
each case lowers and compiles for `v5e:2x2`'s first chip and inspects the
result.  Shapes are those the executor emits for `mico-syn` (the paper's
Table I MiCo stand-in) at the serving capacity with the `auto_buckets`
layout the launchers use.
"""
import dataclasses
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.kernel_contracts import executor_specs
from repro.core.executor import (ExecutorConfig, _bs_iters, _make_count_fn,
                                 auto_buckets)
from repro.core.pattern import clique
from repro.core.plan import build_plan
from repro.core.restrictions import generate_restriction_sets
from repro.graph.datasets import named_dataset
from repro.kernels import ops

HBM_BYTES = 16 * 10**9          # one v5e chip
SERVING_CAPACITY = 1 << 15      # ExecutorConfig / launcher default

pytestmark = pytest.mark.xdist_group("tpu_compile")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # noqa: BLE001 — no TPU compiler here
        if importlib.util.find_spec("libtpu") is not None:
            raise
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip — keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mico():
    g = named_dataset("mico-syn")
    cfg = ExecutorConfig(capacity=SERVING_CAPACITY, use_pallas=True,
                         degree_buckets=auto_buckets(g))
    return g, cfg


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# executor_specs labels each call site's mode: mask (restrictions +
# injectivity), count (last level), iep (signed count, neg_from)
MODES = {
    "mask": lambda s: not s.count,
    "count": lambda s: s.count and s.E == 0 and s.neg_from is None,
    "count_dirs": lambda s: s.count and s.E > 0,
    "count_neg_from": lambda s: s.neg_from is not None,
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_level_expand_compiles_for_v5e(mode, mico, one_chip,
                                       no_compile_cache):
    """The fused kernel at every degree bucket's shape, under x64 as the
    executor traces it, compiles to a Mosaic custom call."""
    g, cfg = mico
    flat_len = len(g.indices) + ops.flat_gather_pad(len(g.indices))
    specs = []
    for s in executor_specs(g.n, g.m, g.max_degree, cfg):
        if mode == "count":          # a last level with no restriction
            s = dataclasses.replace(s, E=0)
        if MODES[mode](s):
            specs.append(s)
    assert len(specs) == len(cfg.degree_buckets)
    for s in specs:
        def call(cand, flat, starts, lens, extra, valid, s=s):
            return ops.level_expand(
                cand, flat, starts, lens, extra, valid,
                dirs=(1,) * s.E, count=s.count, neg_from=s.neg_from)

        args = (_spec((s.B, s.D), one_chip), _spec((flat_len,), one_chip),
                _spec((s.P, s.B), one_chip), _spec((s.P, s.B), one_chip),
                _spec((s.B, s.E), one_chip) if s.E else None,
                _spec((s.B, s.D), one_chip, jnp.bool_))
        with jax.enable_x64(True):
            compiled = jax.jit(call).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), s.label


@pytest.mark.parametrize("bucket", [0, 1, 2])
def test_window_gather_compiles_for_v5e(bucket, mico, one_chip,
                                        no_compile_cache):
    """The candidate-window row gather at each degree bucket's (cap,
    width), under x64 as the executor traces it, compiles to a Mosaic
    custom call with a [cap, width] result."""
    g, cfg = mico
    width, frac = cfg.degree_buckets[bucket]
    width = min(width, g.max_degree)
    cap = max(int(cfg.capacity * frac), 8)
    flat_len = len(g.indices) + ops.flat_gather_pad(len(g.indices))
    with jax.enable_x64(True):
        compiled = jax.jit(lambda f, s: ops.window_gather(
            f, s, width=width)).lower(_spec((flat_len,), one_chip),
                                      _spec((cap,), one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "window_gather" in text
    assert compiled.out_info.shape == (cap, width)


@pytest.fixture(scope="module")
def clique4_program(mico, one_chip):
    """One whole single-device count program (4-clique: levels with two
    and three predecessors, a restricted mask level and a counting last
    level) for mico-syn at the serving capacity, traced under x64 as
    `Matcher` traces it and compiled for the described chip."""
    from jax.experimental.compilation_cache import compilation_cache

    g, cfg = mico
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            # the executor picks the compiled kernels only on a TPU
            # backend; this process's backend is the CPU, so steer that
            # one query here
            mp.setattr(jax, "default_backend", lambda: "tpu")
            assert cfg.pallas_mode() == "1"
            pattern = clique(4)
            rs = generate_restriction_sets(pattern, max_sets=1)[0]
            plan = build_plan(pattern, tuple(range(4)), rs)
            W = g.max_degree
            fn = _make_count_fn(plan, W, _bs_iters(W), cfg)
            flat_len = len(g.indices) + ops.flat_gather_pad(len(g.indices))
            args = (_spec((g.n + 1,), one_chip), _spec((g.n + 1,), one_chip),
                    _spec((flat_len,), one_chip),
                    _spec((cfg.capacity,), one_chip))
            with jax.enable_x64(True):
                compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return compiled, plan, flat_len


def test_count_program_compiles_and_fits_one_chip(clique4_program):
    """The 4-clique count program holds the Mosaic kernel and fits in
    16 GB."""
    compiled, _, flat_len = clique4_program
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, (total, mem)
    assert np.iinfo(np.int32).max > flat_len


def test_count_program_gathers_each_window_once(clique4_program, mico):
    """Each (level, bucket) window is one row gather in the compiled
    program, and no gather there indexes the window's cap × width
    entries one by one: neither the candidates nor the compaction's
    parent rows."""
    compiled, plan, _ = clique4_program
    g, cfg = mico
    text = compiled.as_text()
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "window_gather/pallas_call" in line]
    scopes = sorted({re.search(r"level\d+/w\d+", c).group(0)
                     for c in calls})
    want = sorted(f"level{i}/w{min(w, g.max_degree)}"
                  for i in range(1, plan.depth)
                  for w, _ in cfg.degree_buckets)
    assert scopes == want and len(calls) == len(want), calls
    for w, f in cfg.degree_buckets:
        w, cap = min(w, g.max_degree), max(int(cfg.capacity * f), 8)
        for shape in (f"s32[{cap * w}]", f"s32[{cap},{w}]"):
            assert not re.search(re.escape(shape) + r"\{[^}]*\} gather\(",
                                 text), shape
