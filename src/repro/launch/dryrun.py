import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Roofline mode: Pallas kernels lower as HBM-footprint-equivalent stubs
# (opaque custom calls on real hardware too); their MXU flops are added
# analytically below.  Tests/examples run the real interpret-mode kernels.
os.environ["REPRO_FLASH_STUB"] = "1"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder devices.

For each cell this:
  1. builds abstract params/batch/cache (jax.eval_shape — no allocation),
  2. jits the train/prefill/decode step with the production shardings,
  3. .lower().compile() against the requested mesh,
  4. records memory_analysis / cost_analysis / collective bytes
     (roofline terms) into a JSON artifact.

Also includes the GraphPi cell (`--arch graphpi`): the paper's
distributed counting kernel lowered over the same mesh.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh multi --out artifacts/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np


def model_flops_estimate(cfg, shape) -> float:
    """6·N_active·D for train (fwd+bwd), 2·N_active·D for serving."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch            # one new token per sequence
    return 2.0 * n * tokens


def flash_kernel_flops(cfg, shape, mesh) -> float:
    """Per-DEVICE MXU flops of the stubbed Pallas flash-attention calls.

    Engages only where layers._flash_sharded would: prefill, Sq == Sk,
    S % 512 == 0, hd <= 128.  qk^T + pv = 4·B·H·S²·hd, halved for causal
    masking (block-skipped above the diagonal).  Sharding: batch over the
    data axes and — when H divides |model| — heads over `model`;
    otherwise the kernel is replicated over `model` (dp-only fallback)."""
    if shape.kind != "prefill" or cfg.n_heads == 0:
        return 0.0
    S, B = shape.seq_len, shape.global_batch
    if S % 512 or cfg.head_dim > 128:
        return 0.0
    from ..models.transformer import layer_kinds

    n_causal = sum(1 for k in layer_kinds(cfg) if k == "attn")
    # whisper: bidirectional encoder self-attn + per-decoder-layer cross
    n_full = cfg.enc_layers + (cfg.n_layers if cfg.family == "encdec" else 0)
    per_layer = 4.0 * B * cfg.n_heads * float(S) ** 2 * cfg.head_dim
    total = per_layer * (0.5 * n_causal + n_full)
    mdl = mesh.shape.get("model", 1)
    ndp = 1
    for a in ("pod", "data"):
        if a in mesh.shape and B % (ndp * mesh.shape[a]) == 0:
            ndp *= mesh.shape[a]
    shards = ndp * (mdl if cfg.n_heads % mdl == 0 else 1)
    return total / shards


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str, *,
               opts=None):
    """Lower+compile one cell; returns (compiled, model_flops)."""
    from ..configs import SHAPES, get_config, input_specs
    from ..models import transformer as T
    from ..serve.serve_step import make_decode, make_prefill
    from ..train.optimizer import AdamWConfig, init_opt_state
    from ..train.train_step import TrainOptions, abstract_params, make_train_step

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mf = model_flops_estimate(cfg, shape)

    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            batch_shape = input_specs(cfg, shape)
            opts = opts or TrainOptions()
            step, p_sh, o_sh, b_sh = make_train_step(
                cfg, AdamWConfig(), mesh, opts, batch_shape
            )
            p_shape = abstract_params(cfg)
            o_shape = jax.eval_shape(init_opt_state, p_shape)
            lowered = step.lower(p_shape, o_shape, batch_shape)
        elif shape.kind == "prefill":
            batch_shape = input_specs(cfg, shape)
            step, p_sh, b_sh = make_prefill(cfg, mesh, batch_shape)
            lowered = step.lower(abstract_params(cfg), batch_shape)
        else:  # decode
            step, p_sh, c_sh, cache_shape = make_decode(
                cfg, mesh, batch=shape.global_batch, max_seq=shape.seq_len
            )
            tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = step.lower(abstract_params(cfg), tok, cache_shape, pos)
        compiled = lowered.compile()
    return compiled, mf


def lower_graphpi(mesh, mesh_name: str, *, buckets: bool | None = None):
    """The paper's cell: distributed house-pattern counting on the mesh.

    `buckets` toggles the degree-bucketed expansion (§Perf): None reads
    REPRO_GRAPHPI_BUCKETS (default on; set 0 for the paper-faithful
    single-window baseline).  REPRO_GRAPHPI_MODEL_BUCKETS=1 sizes the
    bucket fractions from the perf model's predicted frontier occupancy
    instead of the legacy 4×-margin heuristic."""
    from ..core.config_search import search_configuration
    from ..core.executor import (
        ExecutorConfig, _bs_iters, _make_count_fn, device_graph,
        auto_buckets,
    )
    from ..core.pattern import house
    from ..core.perf_model import GraphStats
    from ..graph.datasets import rmat
    from jax.sharding import PartitionSpec as P

    if buckets is None:
        buckets = os.environ.get("REPRO_GRAPHPI_BUCKETS", "1") == "1"
    g = rmat(16, 12, seed=0)                 # 65k vertices, ~700k edges
    stats = GraphStats(g.n, g.m, tri_cnt=max(g.m, 1))  # plan-time proxy
    res = search_configuration(house(), stats, use_iep=True)
    plan = res.plan(house())
    model_buckets = os.environ.get("REPRO_GRAPHPI_MODEL_BUCKETS", "0") == "1"
    cfg = ExecutorConfig(
        capacity=1 << 15,
        degree_buckets=auto_buckets(
            g, stats=stats if model_buckets else None) if buckets else None,
    )
    W = max(g.max_degree, 1)
    count_fn = _make_count_fn(plan, W, _bs_iters(W), cfg)
    indptr, degrees, flat = (np.asarray(x) for x in device_graph(g)[:3])

    axes = [a for a in mesh.axis_names if a != "model"]
    nsh = int(np.prod([mesh.shape[a] for a in axes]))
    per = -(-g.n // nsh)
    v0 = np.full(nsh * per, g.n, dtype=np.int32)
    v0[: g.n] = np.arange(g.n, dtype=np.int32)
    v0 = v0.reshape(per, nsh).T.reshape(-1)
    ax = tuple(axes) if len(axes) > 1 else axes[0]

    def shard_fn(indptr, degrees, flat, v0_local):
        cnt, needed, _ = count_fn(indptr, degrees, flat, v0_local)
        return jax.lax.psum(cnt, ax), jax.lax.pmax(needed, ax)


    with jax.enable_x64(True):
        fn = jax.jit(
            jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(), P(), P(), P(ax)),
                out_specs=(P(), P()),
            )
        )
        lowered = fn.lower(
            jax.ShapeDtypeStruct(indptr.shape, indptr.dtype),
            jax.ShapeDtypeStruct(degrees.shape, degrees.dtype),
            jax.ShapeDtypeStruct(flat.shape, flat.dtype),
            jax.ShapeDtypeStruct(v0.shape, v0.dtype),
        )
        compiled = lowered.compile()
    # "model flops" proxy: ~W compares per expanded embedding is data-dep;
    # report 0 and rely on the measured terms for this cell.
    return compiled, 0.0


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str):
    from ..launch.mesh import make_production_mesh
    from ..roofline.analysis import analyze

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    extra_flops = 0.0
    if arch == "graphpi":
        compiled, mf = lower_graphpi(mesh, mesh_name)
    else:
        compiled, mf = lower_cell(arch, shape_name, mesh, mesh_name)
        from ..configs import SHAPES, get_config

        extra_flops = flash_kernel_flops(get_config(arch), SHAPES[shape_name],
                                         mesh)
    dt = time.time() - t0
    r = analyze(arch, shape_name, mesh_name, chips, compiled, mf,
                extra_flops_per_device=extra_flops)
    rec = r.to_json()
    rec["compile_seconds"] = dt
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = str(ma)
    except Exception as e:  # pragma: no cover
        rec["memory_analysis"] = f"unavailable: {e}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(
        f"[dryrun OK] {arch} × {shape_name} × {mesh_name}: "
        f"compile={dt:.1f}s compute={r.compute_s:.4f}s memory={r.memory_s:.4f}s "
        f"collective={r.collective_s:.4f}s bottleneck={r.bottleneck} "
        f"useful={r.useful_flops_ratio:.2f}"
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    from ..configs import ARCHS, supported_shapes

    cells = []
    if args.all:
        for a in ARCHS:
            for s in supported_shapes(a):
                cells.append((a, s))
        cells.append(("graphpi", "count"))
    else:
        assert args.arch, "--arch or --all required"
        shapes = [args.shape] if args.shape else (
            ["count"] if args.arch == "graphpi"
            else supported_shapes(args.arch))
        cells = [(args.arch, s) for s in shapes]

    failures = []
    for a, s in cells:
        try:
            run_cell(a, s, args.mesh, args.out)
        except Exception as e:
            failures.append((a, s, repr(e)))
            print(f"[dryrun FAIL] {a} × {s} × {args.mesh}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")


if __name__ == "__main__":
    main()
