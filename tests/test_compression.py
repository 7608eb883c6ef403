"""Gradient compression with error feedback: accuracy + EF accumulation."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import (allreduce_compressed,
                                           ef_compress, ef_decompress,
                                           ef_init)
from repro.launch.mesh import make_host_mesh


def test_quantize_roundtrip_small_error():
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 0.1}
    ef = ef_init(g)
    comp, ef = ef_compress(g, ef)
    out = ef_decompress(comp)
    rel = float(jnp.linalg.norm(out["w"] - g["w"])
                / jnp.linalg.norm(g["w"]))
    assert rel < 0.02  # int8 per-tensor quantization
    assert comp.q["w"].dtype == jnp.int8


def test_error_feedback_accumulates_to_truth():
    """Repeatedly compressing the SAME gradient, the EF-corrected mean of
    decompressed gradients converges to the true gradient."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (32,)) * 0.05}
    ef = ef_init(g)
    acc = jnp.zeros_like(g["w"])
    n = 20
    for _ in range(n):
        comp, ef = ef_compress(g, ef)
        acc = acc + ef_decompress(comp)["w"]
    rel = float(jnp.linalg.norm(acc / n - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 5e-3  # EF drives the time-averaged error to ~0


def test_allreduce_compressed_single_device():
    mesh = make_host_mesh(1, 1)
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (16, 16)) * 0.1}
    ef = ef_init(g)

    def f(g, ef):
        return allreduce_compressed(g, ef, "data")

    out, ef2 = jax.jit(
        jax.shard_map(f, mesh=mesh,
                      in_specs=(P(), P()), out_specs=(P(), P())))(g, ef)
    rel = float(jnp.linalg.norm(out["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 0.02
