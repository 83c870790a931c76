"""Data-pipeline determinism + gradient-compression correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.train.compress import compressed_psum, dequantize_int8, quantize_int8
from repro.train.data import DataConfig, SyntheticLM


def test_batch_pure_function_of_step():
    cfg = DataConfig(vocab=128, seq_len=16, global_batch=4, seed=3)
    d1, d2 = SyntheticLM(cfg), SyntheticLM(cfg)
    for step in (0, 5, 1000):
        b1, b2 = d1.batch(step), d2.batch(step)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        np.testing.assert_array_equal(b1["labels"], b2["labels"])


def test_batches_differ_across_steps_and_seeds():
    cfg = DataConfig(vocab=512, seq_len=32, global_batch=4, seed=0)
    d = SyntheticLM(cfg)
    assert not np.array_equal(d.batch(0)["tokens"], d.batch(1)["tokens"])
    d2 = SyntheticLM(DataConfig(vocab=512, seq_len=32, global_batch=4, seed=1))
    assert not np.array_equal(d.batch(0)["tokens"], d2.batch(0)["tokens"])


def test_labels_are_next_tokens():
    cfg = DataConfig(vocab=128, seq_len=16, global_batch=2, seed=0)
    b = SyntheticLM(cfg).batch(0)
    np.testing.assert_array_equal(
        np.asarray(b["tokens"][:, 1:]), np.asarray(b["labels"][:, :-1])
    )


def test_family_specific_batches():
    enc = get_smoke_config("whisper-base")
    b = SyntheticLM(DataConfig(vocab=enc.vocab, seq_len=8, global_batch=2),
                    enc).batch(0)
    assert b["enc_embeds"].shape == (2, 8, enc.d_model)
    vlm = get_smoke_config("qwen2-vl-72b")
    b = SyntheticLM(DataConfig(vocab=vlm.vocab, seq_len=8, global_batch=2),
                    vlm).batch(0)
    assert b["embeds"].shape == (2, 8, vlm.d_model)
    assert b["positions3"].shape == (2, 3, 8)


# ----------------------------------------------------------- compression ---
def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6


def test_compressed_psum_single_device_identity_with_error_feedback():
    mesh = jax.make_mesh((1,), ("dp",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(64,))
                          .astype(np.float32))}

    def f(t):
        out, err = compressed_psum(t, "dp")
        return out, err

    out, err = jax.jit(
        jax.shard_map(f, mesh=mesh,
                  in_specs=(jax.sharding.PartitionSpec(),),
                  out_specs=(jax.sharding.PartitionSpec(),) * 2)
    )(g)
    # single device: reduced value == dequantized value; error = residual
    np.testing.assert_allclose(
        np.asarray(out["w"] + err["w"]), np.asarray(g["w"]),
        rtol=0, atol=1e-6,
    )


def test_error_feedback_accumulates_to_true_sum():
    """Simulated repeated reductions: error feedback makes the MEAN of
    compressed reductions converge to the true gradient."""
    mesh = jax.make_mesh((1,), ("dp",))
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32)) * 1e-3

    def f(t, e):
        out, err = compressed_psum(t, "dp", error_state=e)
        return out, err

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),) * 2,
        out_specs=(jax.sharding.PartitionSpec(),) * 2,
    ))
    err = {"g": jnp.zeros_like(g)}
    total = np.zeros_like(np.asarray(g))
    N = 32
    for _ in range(N):
        out, err = fn({"g": g}, err)
        total += np.asarray(out["g"])
    np.testing.assert_allclose(total / N, np.asarray(g), atol=5e-6)
