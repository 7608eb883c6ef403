"""Compiles for a described TPU v5e, at published widths, without a chip.

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, primitives Mosaic cannot lower, programs that do not fit
the chip's memory. Each test compiles one program of the chip path for
one v5e chip and checks what the compiler made of it. Nothing runs.

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

# usable HBM of one v5e chip, as its compiler reports it ("of 15.75G")
V5E_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batch,seq", [(8, 1024), (2, 4096)])
def test_flash_attention_qwen2_widths(one_chip, batch, seq):
    """Forward and backward, with the 2 KV heads not expanded to 14."""
    cfg = get_config("qwen2-0.5b")
    hd = cfg.resolved_head_dim
    q = _on(one_chip, (batch, seq, cfg.num_heads, hd), jnp.bfloat16)
    kv = _on(one_chip, (batch, seq, cfg.num_kv_heads, hd), jnp.bfloat16)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(ops.flash_attention, q, k, v)
        return o, vjp(do)

    text = jax.jit(fwd_bwd).lower(q, kv, kv, q).compile().as_text()
    # forward, dQ and dK/dV kernels
    assert text.count("tpu_custom_call") == 3


# sha256 of the three kernels' Mosaic bodies at qwen2-0.5b's widths, with
# source locations left out, as the kernel compiled before it took q/k and
# v of different widths
QWEN2_KERNEL_DIGESTS = {
    (8, 1024): ["1d6d113fd028", "9e1e0cb7d453", "714ac997fa95"],
    (2, 4096): ["08ba2d9fda55", "4035fe4b04fc", "80f44b3b3038"],
}


@pytest.mark.parametrize("batch,seq", sorted(QWEN2_KERNEL_DIGESTS))
def test_qwen2_kernels_are_unchanged(one_chip, batch, seq):
    import hashlib
    import re

    cfg = get_config("qwen2-0.5b")
    hd = cfg.resolved_head_dim
    q = _on(one_chip, (batch, seq, cfg.num_heads, hd), jnp.bfloat16)
    kv = _on(one_chip, (batch, seq, cfg.num_kv_heads, hd), jnp.bfloat16)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(ops.flash_attention, q, k, v)
        return o, vjp(do)

    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = jax.jit(fwd_bwd).lower(q, kv, kv, q).compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    bodies = re.findall(r'"body":"([^"]*)"', text)
    assert [hashlib.sha256(b.encode()).hexdigest()[:12] for b in bodies] == \
        QWEN2_KERNEL_DIGESTS[batch, seq]


def test_flash_attention_latent_widths(one_chip):
    """Moonlight-16B-A3B's MLA heads at its cell's shape: q/k 192 wide, v
    128, forward and backward at the blocks ``ops`` picks for them."""
    cfg = get_config("moonlight-16b-a3b")
    dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    q = _on(one_chip, (2, 8192, cfg.num_heads, dqk), jnp.bfloat16)
    v = _on(one_chip, (2, 8192, cfg.num_heads, cfg.v_head_dim), jnp.bfloat16)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(ops.flash_attention, q, k, v)
        return o, vjp(do)

    text = jax.jit(fwd_bwd).lower(q, q, v, v).compile().as_text()
    assert text.count("tpu_custom_call") == 3


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-370m"])
def test_rmsnorm_published_widths(one_chip, arch):
    d = get_config(arch).d_model
    x = _on(one_chip, (8 * 1024, d), jnp.bfloat16)
    g = _on(one_chip, (d,), jnp.float32)
    compiled = ops.rmsnorm.lower(x, g).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_mamba2_widths(one_chip):
    cfg = get_config("mamba2-370m")
    b, l, h, p, n = 4, 1024, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert (h, p, n, cfg.ssm_chunk) == (32, 64, 128, 256)
    compiled = ops.ssd_scan.lower(
        _on(one_chip, (b, l, h, p), jnp.bfloat16),
        _on(one_chip, (b, l, h), jnp.float32),
        _on(one_chip, (h,), jnp.float32),
        _on(one_chip, (b, l, n), jnp.bfloat16),
        _on(one_chip, (b, l, n), jnp.bfloat16),
        chunk=cfg.ssm_chunk).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_launcher_step_qwen2_fits_one_chip(one_chip):
    """The launcher's donated full-width step at the chip smoke's shape."""
    from repro.models import build_model
    from repro.train import optimizer as opt_lib
    from repro.train import step as step_lib
    from repro.train.loop import LoopConfig, Trainer

    model = build_model(get_config("qwen2-0.5b"))
    opt_cfg = opt_lib.OptConfig()
    trainer = Trainer(model, opt_cfg, LoopConfig(batch=8, seq=1024))
    on_chip = lambda t: jax.tree.map(
        lambda s: _on(one_chip, s.shape, s.dtype), t)
    state = on_chip(step_lib.state_shapes(model, opt_cfg))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((8, 1024), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((8, 1024), jnp.int32)})
    compiled = trainer.jstep.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes  # donated
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total / 2**30


def test_launcher_step_with_the_kernel_at_s4096(one_chip):
    """The donated step at b2 x s4096 with attention on the kernel: it
    compiles, holds the kernels and fits one chip."""
    from repro.models import build_model
    from repro.models.attention import attention_impl
    from repro.train import optimizer as opt_lib
    from repro.train import step as step_lib
    from repro.train.loop import LoopConfig, Trainer

    with attention_impl("pallas"):
        model = build_model(get_config("qwen2-0.5b"))
        opt_cfg = opt_lib.OptConfig()
        trainer = Trainer(model, opt_cfg, LoopConfig(batch=2, seq=4096))
        on_chip = lambda t: jax.tree.map(
            lambda s: _on(one_chip, s.shape, s.dtype), t)
        state = on_chip(step_lib.state_shapes(model, opt_cfg))
        batch = on_chip({"tokens": jax.ShapeDtypeStruct((2, 4096), jnp.int32),
                         "labels": jax.ShapeDtypeStruct((2, 4096), jnp.int32)})
        compiled = trainer.jstep.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total / 2**30


def test_moonlight_cell_step_fits_one_chip(one_chip):
    """The benchmark cell's donated step: Moonlight-16B-A3B cut to one
    chip's share (the dense layer and 4 expert layers, 8 of 64 experts,
    20480 vocabulary rows) at b2 x s8192, with MLA on the kernel. It
    compiles, holds the flash kernels and the megablox grouped matmuls, and
    fits."""
    import dataclasses

    from repro.models import build_model
    from repro.models.attention import attention_impl
    from repro.train import optimizer as opt_lib
    from repro.train import step as step_lib
    from repro.train.loop import LoopConfig, Trainer

    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), num_layers=5,
                              experts_held=8, vocab_size=20480)
    # the kernel paths a TPU takes: the flash kernel, the grouped matmul
    with attention_impl("pallas"):
        model = build_model(cfg)
        opt_cfg = opt_lib.OptConfig()
        trainer = Trainer(model, opt_cfg, LoopConfig(batch=2, seq=8192))
        on_chip = lambda t: jax.tree.map(
            lambda s: _on(one_chip, s.shape, s.dtype), t)
        state = on_chip(step_lib.state_shapes(model, opt_cfg))
        batch = on_chip({"tokens": jax.ShapeDtypeStruct((2, 8192), jnp.int32),
                         "labels": jax.ShapeDtypeStruct((2, 8192), jnp.int32)})
        compiled = trainer.jstep.lower(state, batch).compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and "gmm" in text
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total / 2**30
