"""Shared neural building blocks (pure JAX, param pytrees = nested dicts).

Conventions:
 * params are fp32 masters; `cast` converts activations/weights to the
   compute dtype at use sites (mixed precision);
 * every init_* is pure-jax (traceable under jax.eval_shape for the
   dry-run: parameter shapes without allocation);
 * batch is logically sharded over the mesh data axes, d_ff/heads over
   the model axis (see parallel/sharding.py).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

Params = dict


def cast(x, dtype):
    return x.astype(dtype) if x.dtype != dtype else x


def init_dense(key, d_in: int, d_out: int, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}


def dense(p: Params, x, dtype):
    return x @ cast(p["w"], dtype)


def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(p: Params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["scale"]).astype(dt)


def head_rms_norm(x, scale, eps: float = 1e-6):
    """Per-head q/k norm (Qwen3 qk_norm): x [..., head_dim]."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(dt)


# ------------------------------------------------------------------ RoPE
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x, positions, theta: float):
    """x [..., S, H, hd]; positions [..., S] (int)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, hd/2]
    ang = ang[..., None, :]                              # [..., S, 1, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections=(16, 24, 24)):
    """Qwen2-VL M-RoPE: positions3 [..., 3, S] (t, h, w components);
    head_dim/2 frequency slots are split across the 3 components.

    `sections` are the qwen2-vl mrope_section values (sum = hd/2).
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)                        # [hd/2]
    # per-frequency component selector
    comp = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )                                                    # [hd/2]
    p3 = jnp.moveaxis(positions3, -2, -1)                # [..., S, 3]
    pos = p3[..., comp]                                  # [..., S, hd/2]
    ang = pos.astype(jnp.float32) * freqs                # [..., S, hd/2]
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ------------------------------------------------------------------ MLP
def init_mlp(key, d: int, ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": init_dense(k1, d, ff),
        "up": init_dense(k2, d, ff),
        "down": init_dense(k3, ff, d),
    }


def swiglu_mlp(p: Params, x, dtype):
    g = dense(p["gate"], x, dtype)
    u = dense(p["up"], x, dtype)
    return dense(p["down"], jax.nn.silu(g) * u, dtype)


# ------------------------------------------------------------- attention
def init_attention(key, cfg) -> Params:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], d, H * hd),
        "wk": init_dense(ks[1], d, K * hd),
        "wv": init_dense(ks[2], d, K * hd),
        "wo": init_dense(ks[3], H * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _qkv(p, x, cfg, dtype, positions=None, positions3=None):
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(dense(p["wq"], x, dtype), H, hd)
    k = _split_heads(dense(p["wk"], x, dtype), K, hd)
    v = _split_heads(dense(p["wv"], x, dtype), K, hd)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.mrope and positions3 is not None:
        q = apply_mrope(q, positions3, cfg.rope_theta, _mrope_sections(hd))
        k = apply_mrope(k, positions3, cfg.rope_theta, _mrope_sections(hd))
    elif positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mrope_sections(hd: int):
    # qwen2-vl uses (16, 24, 24) for hd=128; scale proportionally otherwise
    base = (16, 24, 24)
    if hd // 2 == sum(base):
        return base
    unit = (hd // 2) // 4
    return (unit, (hd // 2 - unit) // 2, hd // 2 - unit - (hd // 2 - unit) // 2)


def _sdpa(q, k, v, *, causal: bool, q_offset=0):
    """q [B,Sq,H,hd], k/v [B,Sk,K,hd] — grouped-query attention."""
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    q = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k) / math.sqrt(hd)
    scores = scores.astype(jnp.float32)
    if causal:
        qi = jnp.arange(Sq)[:, None] + q_offset
        ki = jnp.arange(Sk)[None, :]
        scores = jnp.where(qi >= ki, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def _sdpa_chunked(q, k, v, *, causal: bool, chunk: int = 1024):
    """Memory-efficient attention: lax.scan over query chunks (flash-style
    running softmax is unnecessary when the k/v fit — we chunk queries so
    the [Sq, Sk] score matrix never fully materializes)."""
    B, Sq, H, hd = q.shape
    if Sq <= chunk:
        return _sdpa(q, k, v, causal=causal)
    n = Sq // chunk
    assert Sq % chunk == 0, (Sq, chunk)
    qs = q.reshape(B, n, chunk, H, hd).transpose(1, 0, 2, 3, 4)

    def step(carry, args):
        i, qc = args
        out = _sdpa(qc, k, v, causal=causal, q_offset=i * chunk)
        return carry, out

    _, outs = jax.lax.scan(step, (), (jnp.arange(n), qs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def _ambient_model_axis():
    """(model_axis_size, dp_axes) from the ambient mesh, or (1, ())."""

    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # pragma: no cover
        return 1, ()
    if mesh is None or not getattr(mesh, "axis_names", None):
        return 1, ()
    names = mesh.axis_names
    if "model" not in names:
        return 1, ()
    dp = tuple(a for a in names if a in ("pod", "data"))
    return mesh.shape["model"], dp


def _seq_shard_qkv(q, k, v):
    """KV-sequence sharding for head counts that do not divide the model
    axis (§Perf iteration 2, minitron-4b: 24 heads on a 16-way axis made
    GSPMD replicate/all-gather the score tensors — 542 s of ICI per
    prefill step).  Instead: replicate q over `model`, shard K/V along
    the sequence; scores/softmax/out then contract the sharded key axis
    locally and GSPMD inserts only the small softmax-stat and output
    psums (flash-decoding style, applied to prefill/train)."""
    m, dp = _ambient_model_axis()
    H = q.shape[2]
    Sk = k.shape[1]
    if m <= 1 or H % m == 0 or Sk % m != 0:
        return q, k, v
    from jax.sharding import PartitionSpec as P

    bspec = dp if dp else None
    q = jax.lax.with_sharding_constraint(q, P(bspec, None, None, None))
    k = jax.lax.with_sharding_constraint(k, P(bspec, "model", None, None))
    v = jax.lax.with_sharding_constraint(v, P(bspec, "model", None, None))
    return q, k, v


def _flash_sharded(q, k, v, *, causal: bool):
    """Route self-attention through the Pallas flash kernel when shapes
    and sharding allow; returns None to fall back to the XLA path.

    §Perf iteration 3: the XLA path materializes fp32 score tensors at
    fusion boundaries (the dominant prefill memory term); the kernel
    keeps them in VMEM.  Distribution: shard_map with batch over the
    data axes and q-heads over `model` (each shard gathers its matching
    kv heads — zero-copy GQA inside the shard).  Head counts that do not
    divide `model` fall back to data-only sharding (attention compute
    replicated over `model`; still memory-optimal)."""
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    if Sq != Sk or Sq % 512 or hd > 128:
        return None
    import os

    from ..kernels.ops import flash_attention as _real_flash

    if os.environ.get("REPRO_FLASH_STUB") == "1":
        # Dry-run roofline mode: the Pallas kernel is an opaque custom
        # call on real hardware (cost_analysis cannot see inside it
        # there either), and its interpret-mode HLO emulation has a
        # wildly different byte profile.  Substitute an op with the
        # kernel's exact HBM footprint — read q,k,v, write o — and let
        # launch/dryrun add the MXU flops analytically.
        def flash_attention(ql, kl, vl, causal=True):
            scale = (kl.mean() + vl.mean()).astype(ql.dtype)
            return ql * scale
    else:
        flash_attention = _real_flash

    m, dp = _ambient_model_axis()
    if m <= 1 and not dp:
        return flash_attention(q, k, v, causal=causal)
    import numpy as np
    from jax.sharding import PartitionSpec as P


    mesh = jax.sharding.get_abstract_mesh()
    ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if dp and B % ndp:
        dp = dp[:-1]
        ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        if dp and B % ndp:
            return None
    bspec = dp if dp else None
    head_sharded = H % m == 0 and m > 1
    G = H // K

    if head_sharded:
        qspec = P(bspec, None, "model", None)
        kvspec = P(bspec, None, None, None)

        def local(ql, kl, vl):
            Hl = ql.shape[2]
            off = jax.lax.axis_index("model") * Hl
            kvidx = (off + jnp.arange(Hl, dtype=jnp.int32)) // G
            kl = jnp.take(kl, kvidx, axis=2)
            vl = jnp.take(vl, kvidx, axis=2)
            return flash_attention(ql, kl, vl, causal=causal)

        return jax.shard_map(
            local, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
            out_specs=qspec, check_vma=False,
        )(q, k, v)

    spec = P(bspec, None, None, None)
    return jax.shard_map(
        lambda ql, kl, vl: flash_attention(ql, kl, vl, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def sdpa_any(q, k, v, *, causal: bool, q_chunk: int = 0, flash: bool = False):
    """Dispatch: Pallas flash (serving) → chunked XLA → plain XLA."""
    if flash:
        out = _flash_sharded(q, k, v, causal=causal)
        if out is not None:
            return out
    q, k, v = _seq_shard_qkv(q, k, v)
    if q_chunk:
        return _sdpa_chunked(q, k, v, causal=causal, chunk=q_chunk)
    return _sdpa(q, k, v, causal=causal)


def attention(
    p: Params, x, cfg, dtype, *,
    causal=True, positions=None, positions3=None, q_chunk: int = 0,
    flash: bool = False,
):
    q, k, v = _qkv(p, x, cfg, dtype, positions, positions3)
    out = sdpa_any(q, k, v, causal=causal, q_chunk=q_chunk, flash=flash)
    B, S = x.shape[:2]
    return dense(p["wo"], out.reshape(B, S, -1), dtype)


def cross_attention(p: Params, x, enc_kv, cfg, dtype, *, q_chunk: int = 0,
                    flash: bool = False):
    """x [B,Sq,d]; enc_kv = (k, v) precomputed from encoder output."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(dense(p["wq"], x, dtype), H, hd)
    k, v = enc_kv
    out = sdpa_any(q, k, v, causal=False, q_chunk=q_chunk, flash=flash)
    B, S = x.shape[:2]
    return dense(p["wo"], out.reshape(B, S, -1), dtype)


def enc_kv(p: Params, enc_out, cfg, dtype):
    K, hd = cfg.n_kv_heads, cfg.head_dim
    k = _split_heads(dense(p["wk"], enc_out, dtype), K, hd)
    v = _split_heads(dense(p["wv"], enc_out, dtype), K, hd)
    return k, v


# --------------------------------------------------- decode (KV cache) ----
def attention_decode(p: Params, x, cache_k, cache_v, pos, cfg, dtype,
                     positions3=None):
    """One-token decode: x [B,1,d]; cache [B,S,K,hd]; pos scalar int OR a
    per-row ``[B]`` int vector (continuous batching: each slot of the
    padded batch sits at its own sequence position — admissions mid-
    decode are what make the vector form necessary, DESIGN.md §5).

    The cache sequence axis may be sharded over the mesh `model` axis;
    the softmax reductions below are partitioner-safe (GSPMD inserts the
    cross-shard all-reduces — flash-decoding style).
    """
    B = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    posv = pos[:, None] if per_row else jnp.full((B, 1), pos, jnp.int32)
    if cfg.mrope and positions3 is None:
        positions3 = jnp.broadcast_to(posv[:, None, :], (B, 3, 1))
    q, k, v = _qkv(p, x, cfg, dtype, posv, positions3)
    if per_row:
        # each row writes its token at its own position
        row_upd = jax.vmap(
            lambda c, u, pp: jax.lax.dynamic_update_slice(c, u, (pp, 0, 0)))
        cache_k = row_upd(cache_k, k.astype(cache_k.dtype), pos)
        cache_v = row_upd(cache_v, v.astype(cache_v.dtype), pos)
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0)
        )
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0)
        )
    S = cache_k.shape[1]
    G = H // K
    qh = q.reshape(B, 1, K, G, hd)
    scores = jnp.einsum(
        "bqkgh,bskh->bkgqs", qh, cache_k.astype(dtype)
    ) / math.sqrt(hd)
    scores = scores.astype(jnp.float32)
    # [B,1,1,1,S] per-row causal horizon (broadcasts over heads/groups)
    mask = (jnp.arange(S)[None, :] <= posv)[:, None, None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, cache_v.astype(dtype))
    out = out.reshape(B, 1, H * hd)
    return dense(p["wo"], out, dtype), cache_k, cache_v
