"""LMSession — the LM serving loop as a reusable, resumable object.

`launch/serve.py`'s monolithic main() owned the whole prefill → decode
pipeline inline, which made the loop unschedulable (nothing else could
run between decode steps) and non-restartable (checkpoints were written
but never read).  LMSession splits it into explicit phases:

    session = LMSession("qwen3-1.7b", smoke=True, batch=4,
                        prompt_len=64, gen=32,
                        ckpt_dir=d, ckpt_every=8)
    session.start(resume=True)       # prefill — or restore mid-decode
    while session.remaining:
        session.decode_steps(4)      # any step granularity
    tokens = session.tokens_out()

so the Gateway can interleave decode steps with graph-query rounds on
the shared mesh (`LMDecodeWorkload` in gateway.py), and a preempted
serving process restarts from the last `--ckpt-every` checkpoint
(`start(resume=True)` reloads cache + tokens + step and continues
decoding — the restore path the checkpoint hooks always promised).

CONTINUOUS BATCHING (DESIGN.md §5): the decode program takes a per-row
position vector, so the padded batch's slots need not be in lockstep —
`admit()` prefills ONE new sequence (a lazily-built batch-1 prefill
program) and scatters its cache row into a free slot mid-decode, and
`evict(slot)` frees the row and returns its tokens.  Slot occupancy is
surfaced through `repro.obs` metrics (`lm.slots_active`, `lm.admitted`,
`lm.evicted`) when a registry is attached.

The checkpoint is {"cache", "tokens"} under step k via train.checkpoint
(atomic rename + LATEST pointer); k is the number of decode steps
already applied, so resumed decoding continues at position S + k
(checkpoints cover the uniform lockstep mode; per-slot admission state
is process-local).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import get_tracer, timer


def fake_prompts(cfg, B, S, key):
    """Synthetic prompt batch matching the config family's input spec."""
    if cfg.stub_frontend and cfg.family == "vlm":
        return {
            "embeds": jax.random.normal(key, (B, S, cfg.d_model), jnp.bfloat16),
            "positions3": jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, 3, S)
            ),
        }
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab, jnp.int32)}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            key, (B, S, cfg.d_model), jnp.bfloat16
        )
    return batch


def seed_cache(cache, prefill_cache, S):
    """Copy prefill K/V (length S) into the front of the decode cache."""

    def put(dst, src):
        if dst.ndim >= 2 and src.ndim == dst.ndim and src.shape != dst.shape:
            # K/V: [..., S, K, hd] into [..., max_seq, K, hd]
            ax = next(
                i for i in range(dst.ndim) if src.shape[i] != dst.shape[i]
            )
            idx = [slice(None)] * dst.ndim
            idx[ax] = slice(0, src.shape[ax])
            return dst.at[tuple(idx)].set(src.astype(dst.dtype))
        return src.astype(dst.dtype) if src.shape == dst.shape else dst

    if "blocks" in prefill_cache:
        new_blocks = jax.tree.map(put, cache["blocks"], prefill_cache["blocks"])
        cache = {**cache, "blocks": new_blocks}
    if "cross_kv" in prefill_cache:
        cache = {**cache, "cross_kv": put(cache["cross_kv"],
                                          prefill_cache["cross_kv"])}
    return cache


def _scatter_row(dst, src, b: int):
    """Write a batch-1 cache leaf into row `b` of the live batch-B leaf
    (continuous-batching admission).  The batch axis is located
    structurally: the unique axis where src is 1 and dst is B; every
    other axis matches because both are decode-shaped (same max_seq)."""
    if src.shape == dst.shape:          # B == 1: the row IS the cache
        return src.astype(dst.dtype)
    ax = next(i for i in range(dst.ndim)
              if src.shape[i] == 1 and dst.shape[i] != 1)
    idx = [slice(None)] * dst.ndim
    idx[ax] = b
    return dst.at[tuple(idx)].set(
        jnp.squeeze(src, axis=ax).astype(dst.dtype))


class LMSession:
    """One batched generation: prefill once, then stepwise greedy decode.

    Parameters mirror `launch/serve.py`'s CLI.  `mesh=None` builds the
    host mesh; pass the Gateway's mesh to co-schedule with other
    workloads on the same devices.
    """

    def __init__(self, arch: str, *, smoke: bool = False, batch: int = 4,
                 prompt_len: int = 64, gen: int = 32, max_seq: int = 0,
                 mesh=None, model_axis: int = 1, seed: int = 0,
                 ckpt_dir: str = "", ckpt_every: int = 0,
                 metrics=None):
        from ..configs import get_config, get_smoke_config
        from ..launch.mesh import make_host_mesh

        self.arch = arch
        self.cfg = get_smoke_config(arch) if smoke else get_config(arch)
        self.mesh = mesh if mesh is not None else make_host_mesh(
            model=model_axis)
        self.B = batch
        self.S = prompt_len
        self.gen = gen
        self.max_seq = max_seq or (prompt_len + gen)
        self.seed = seed
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self._metrics = metrics         # optional obs.MetricsRegistry
        self._params = None
        self._decode = None
        self._cache = None
        self._tokens = None
        self._generated: list[np.ndarray] = []
        self.step_i = 0                 # decode steps already applied
        self.resumed_from: int | None = None
        self.prefill_seconds = 0.0
        self.decode_seconds = 0.0
        # continuous-batching slot state (uniform lockstep until the
        # first admit()/evict() call perturbs it)
        self._pos = None                # np int32 [B]: next write position
        self._active = [False] * batch  # admitted & not evicted
        self._budget = [0] * batch      # decode steps granted per slot
        self._taken = [0] * batch       # decode steps consumed per slot
        self._slot_tokens = {}          # slot -> [int] generated tokens
        self._prefill1 = None           # lazy batch-1 admission prefill
        self._cache1_sh = None
        self.admitted = 0
        self.evicted = 0

    # ----------------------------------------------------------- lifecycle
    def start(self, *, resume: bool = False) -> int | None:
        """Prefill — or, with `resume=True` and a checkpoint present,
        restore cache/tokens/step and skip the prefill entirely.
        Returns the restored step (None for a fresh start)."""
        from ..models import transformer as T
        from .serve_step import make_decode

        key = jax.random.PRNGKey(self.seed)
        with jax.sharding.set_mesh(self.mesh):
            # param init + decode-program build dominate cold start; a
            # leaf span keeps warmup time attributable in traces
            with get_tracer().span("lm.init", arch=self.arch,
                                   batch=self.B):
                self._params = jax.block_until_ready(
                    jax.jit(lambda k: T.init(self.cfg, k))(key))
                self._decode, _, c_sh, self._cache_shape = make_decode(
                    self.cfg, self.mesh, batch=self.B,
                    max_seq=self.max_seq
                )
            restored = self._try_restore() if resume else None
            if restored is None:
                self._prefill(key, c_sh)
            else:
                self.resumed_from = self.step_i = restored
            self._init_slots(self.step_i)
        return self.resumed_from

    def _init_slots(self, at_step: int) -> None:
        """Every row starts occupied, in lockstep at position S+step —
        the legacy uniform batch; admit()/evict() diverge from here."""
        self._pos = np.full(self.B, self.S + at_step, np.int32)
        self._active = [True] * self.B
        self._budget = [self.gen] * self.B
        self._taken = [at_step] * self.B
        toks = np.asarray(self._tokens)
        self._slot_tokens = {b: [int(toks[b, 0])] for b in range(self.B)}
        self._slots_gauge()

    def _slots_gauge(self) -> None:
        if self._metrics is not None:
            self._metrics.gauge("lm.slots_active").set(sum(self._active))

    def _prefill(self, key, c_sh) -> None:
        from ..configs import input_specs
        from ..configs.base import ShapeConfig
        from ..models import transformer as T
        from .serve_step import make_prefill

        with get_tracer().span("lm.build", batch=self.B,
                               prompt_len=self.S):
            shape = ShapeConfig("serve", self.S, self.B, "prefill")
            batch = fake_prompts(self.cfg, self.B, self.S, key)
            prefill, _, _ = make_prefill(
                self.cfg, self.mesh, input_specs(self.cfg, shape),
                q_chunk=0)
        with get_tracer().span("lm.prefill", arch=self.arch, batch=self.B,
                               prompt_len=self.S), timer() as t:
            logits, prefill_cache = jax.block_until_ready(
                prefill(self._params, batch))
        self.prefill_seconds = t.seconds
        with get_tracer().span("lm.cache_init", batch=self.B,
                               max_seq=self.max_seq):
            cache = jax.jit(
                lambda: T.init_cache(self.cfg, self.B, self.max_seq),
                out_shardings=c_sh,
            )()
            self._cache = jax.block_until_ready(
                seed_cache(cache, prefill_cache, self.S))
        self._tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        self._generated = [np.asarray(self._tokens)]

    def _try_restore(self) -> int | None:
        from ..train import checkpoint as ckpt

        if not self.ckpt_dir:
            return None
        step = ckpt.latest_step(self.ckpt_dir)
        if step is None:
            return None
        tree_like = {
            "cache": self._cache_shape,
            "tokens": jax.ShapeDtypeStruct((self.B, 1), jnp.int32),
        }
        tree, step = ckpt.restore(self.ckpt_dir, tree_like, step=step)
        self._cache = tree["cache"]
        self._tokens = tree["tokens"]
        # generation up to `step` happened in the previous process;
        # tokens_out() covers the resumed suffix only
        self._generated = [np.asarray(self._tokens)]
        return step

    # -------------------------------------------------------------- decode
    @property
    def remaining(self) -> int:
        """Decode steps still owed to the hungriest live slot (the
        legacy ``gen - step_i`` until admissions diverge budgets)."""
        live = [self._budget[b] - self._taken[b]
                for b in range(self.B)
                if self._active[b] and self._taken[b] < self._budget[b]]
        if self._pos is None:           # start() not called yet
            return max(self.gen - self.step_i, 0)
        return max(live, default=0)

    def decode_steps(self, k: int) -> int:
        """Run up to `k` greedy decode steps (bounded by `remaining`);
        checkpoints cache+tokens every `ckpt_every` steps.  Returns the
        number of steps actually run, blocking on the last one so the
        caller's timing covers real device work.

        Every step advances the WHOLE padded batch one token at each
        row's own position (rows past their budget still compute — that
        is the price of a static batch shape — but their tokens are not
        recorded, and their cache rows are re-seeded on admit())."""
        if self._decode is None:
            raise RuntimeError("LMSession.start() must run first")
        from ..train import checkpoint as ckpt

        n = min(max(k, 0), self.remaining)
        if n == 0:
            return 0
        with get_tracer().span("lm.decode", arch=self.arch, steps=n,
                               at_step=self.step_i), timer() as t:
            with jax.sharding.set_mesh(self.mesh):
                for _ in range(n):
                    i = self.step_i
                    pos = jnp.asarray(self._pos)
                    logits, self._cache = self._decode(
                        self._params, self._tokens, self._cache, pos)
                    self._tokens = jnp.argmax(
                        logits, axis=-1).astype(jnp.int32)[:, None]
                    toks = np.asarray(self._tokens)
                    self._generated.append(toks)
                    for b in range(self.B):
                        if self._active[b] and self._taken[b] < self._budget[b]:
                            self._slot_tokens[b].append(int(toks[b, 0]))
                            self._taken[b] += 1
                    # dead rows park at the last cache cell (their writes
                    # are discarded on the next admission)
                    self._pos = np.minimum(self._pos + 1, self.max_seq - 1)
                    self.step_i = i + 1
                    if (self.ckpt_dir and self.ckpt_every
                            and self.step_i % self.ckpt_every == 0):
                        ckpt.save(
                            self.ckpt_dir, self.step_i,
                            {"cache": self._cache, "tokens": self._tokens})
                jax.block_until_ready(self._tokens)
        self.decode_seconds += t.seconds
        return n

    # ------------------------------------------------ continuous batching
    def slots(self) -> dict:
        """Occupancy snapshot: slot -> {active, pos, taken, budget}."""
        return {b: {"active": self._active[b],
                    "pos": None if self._pos is None else int(self._pos[b]),
                    "taken": self._taken[b],
                    "budget": self._budget[b]}
                for b in range(self.B)}

    def admit(self, *, seed: int | None = None,
              gen: int | None = None) -> int:
        """Join ONE new sequence to the running batch: prefill it with a
        lazily-built batch-1 program, scatter its KV/state rows into the
        first free slot's cache rows, and start it at position S — the
        other slots' tokens are untouched (their rows are never
        written).  Returns the slot index; raises when no slot is free.
        """
        if self._decode is None:
            raise RuntimeError("LMSession.start() must run first")
        free = [b for b in range(self.B) if not self._active[b]]
        if not free:
            raise RuntimeError(
                f"no free slot (batch={self.B} all active) — evict first")
        slot = free[0]
        if seed is None:
            seed = self.seed + 1009 * (self.admitted + 1)

        with get_tracer().span("lm.admit", slot=slot, seed=seed), \
                jax.sharding.set_mesh(self.mesh), timer() as t:
            row_cache, token = self._prefill_one(seed)
            self._cache = jax.block_until_ready(jax.tree.map(
                lambda dst, src: _scatter_row(dst, src, slot),
                self._cache, row_cache))
            tokens = np.asarray(self._tokens).copy()
            tokens[slot, 0] = token
            self._tokens = jnp.asarray(tokens)
        self.prefill_seconds += t.seconds
        self._pos[slot] = self.S
        self._active[slot] = True
        self._budget[slot] = self.gen if gen is None else max(int(gen), 0)
        self._taken[slot] = 0
        self._slot_tokens[slot] = [int(token)]
        self.admitted += 1
        if self._metrics is not None:
            self._metrics.counter("lm.admitted").inc()
        self._slots_gauge()
        return slot

    def evict(self, slot: int) -> np.ndarray:
        """Free a slot and return its generated tokens (prefill argmax
        first, then one per recorded decode step)."""
        if not (0 <= slot < self.B) or not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        out = np.asarray(self._slot_tokens[slot], np.int32)
        self._active[slot] = False
        self.evicted += 1
        if self._metrics is not None:
            self._metrics.counter("lm.evicted").inc()
        self._slots_gauge()
        return out

    def _prefill_one(self, seed: int):
        """Batch-1 prefill for admissions: returns (decode-shaped cache
        with batch 1, first generated token).  The program and cache
        shapes are built once and reused for every admission."""
        from ..configs import input_specs
        from ..configs.base import ShapeConfig
        from ..models import transformer as T
        from .serve_step import make_decode, make_prefill

        if self._prefill1 is None:
            shape = ShapeConfig("serve", self.S, 1, "prefill")
            self._prefill1, _, _ = make_prefill(
                self.cfg, self.mesh, input_specs(self.cfg, shape), q_chunk=0)
            # batch-1 decode-shaped cache shardings (for seed_cache)
            _, _, self._cache1_sh, _ = make_decode(
                self.cfg, self.mesh, batch=1, max_seq=self.max_seq)
        key = jax.random.PRNGKey(seed)
        batch = fake_prompts(self.cfg, 1, self.S, key)
        logits, prefill_cache = jax.block_until_ready(
            self._prefill1(self._params, batch))
        cache1 = jax.jit(
            lambda: T.init_cache(self.cfg, 1, self.max_seq),
            out_shardings=self._cache1_sh,
        )()
        cache1 = seed_cache(cache1, prefill_cache, self.S)
        token = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
        return cache1, token

    # ----------------------------------------------------------- reporting
    def tokens_out(self) -> np.ndarray:
        """[B, steps+1] generated tokens (since resume, when resumed)."""
        return np.concatenate(self._generated, axis=1)

    def metrics(self) -> dict:
        steps = self.step_i - (self.resumed_from or 0)
        tok_s = (steps * self.B / self.decode_seconds
                 if self.decode_seconds > 0 else 0.0)
        return {
            "arch": self.arch,
            "batch": self.B,
            "prompt_len": self.S,
            "steps_done": self.step_i,
            "steps_total": self.gen,
            "resumed_from": self.resumed_from,
            "prefill_seconds": self.prefill_seconds,
            "decode_seconds": self.decode_seconds,
            "decode_tok_s": tok_s,
            "ms_per_step": (1e3 * self.decode_seconds / steps
                            if steps else 0.0),
            "admitted": self.admitted,
            "evicted": self.evicted,
            "slots_active": sum(self._active),
        }
