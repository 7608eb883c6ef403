"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("b,s,h,hd", [
    (1, 128, 1, 32), (2, 256, 4, 64), (1, 384, 3, 64), (2, 128, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_allclose(b, s, h, hd, dtype, causal):
    q = _rand(1, (b, s, h, hd), dtype)
    k = _rand(2, (b, s, h, hd), dtype)
    v = _rand(3, (b, s, h, hd), dtype)
    o = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, hd)
    want = jnp.moveaxis(
        ref.attention_ref(qf, kf, vf, causal).reshape(b, h, s, hd), 1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 64, 2, 16, 8, 16), (2, 128, 4, 32, 16, 32), (1, 96, 1, 64, 32, 32),
])
def test_ssd_scan_allclose(b, l, h, p, n, chunk):
    from repro.models.ssm import ssd_chunked_ref
    xb = _rand(4, (b, l, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(5, (b, l, h), jnp.float32))
    a_neg = -jnp.exp(_rand(6, (h,), jnp.float32) * 0.3)
    bm = _rand(7, (b, l, n), jnp.float32) * 0.5
    cm = _rand(8, (b, l, n), jnp.float32) * 0.5
    y, s_fin = ops.ssd_scan(xb, dt, a_neg, bm, cm, chunk, interpret=True)
    yw, sw = ssd_chunked_ref(xb, dt, a_neg, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(sw),
                               atol=2e-4, rtol=2e-4)


def test_ssd_ref_matches_naive_recurrence():
    """Chunked SSD == step-by-step state recurrence."""
    from repro.models.ssm import ssd_chunked_ref
    b, l, h, p, n = 1, 32, 2, 8, 4
    xb = np.asarray(_rand(10, (b, l, h, p), jnp.float32)) * 0.5
    dt = np.asarray(jax.nn.softplus(_rand(11, (b, l, h), jnp.float32)))
    a_neg = np.asarray(-jnp.exp(_rand(12, (h,), jnp.float32) * 0.3))
    bm = np.asarray(_rand(13, (b, l, n), jnp.float32)) * 0.5
    cm = np.asarray(_rand(14, (b, l, n), jnp.float32)) * 0.5
    # naive
    s = np.zeros((b, h, n, p))
    ys = np.zeros((b, l, h, p))
    for t in range(l):
        a = np.exp(dt[:, t] * a_neg[None, :])  # (b,h)
        s = s * a[..., None, None] + np.einsum(
            "bn,bhp->bhnp", bm[:, t], xb[:, t])
        ys[:, t] = np.einsum("bn,bhnp->bhp", cm[:, t], s)
    y, s_fin = ssd_chunked_ref(jnp.asarray(xb), jnp.asarray(dt),
                               jnp.asarray(a_neg), jnp.asarray(bm),
                               jnp.asarray(cm), chunk=8)
    np.testing.assert_allclose(np.asarray(y), ys, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_fin), s, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("r,d", [(64, 128), (256, 64), (32, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_allclose(r, d, dtype):
    x = _rand(20, (r, d), dtype)
    g = _rand(21, (d,), jnp.float32)
    o = ops.rmsnorm(x, g, interpret=True)
    want = ref.rmsnorm_ref(x, g)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_untileable_shapes_raise():
    """No fallback: a shape off the TPU tiling is refused, not shrunk."""
    with pytest.raises(ValueError, match="tile"):
        ops.rmsnorm(jnp.ones((300, 64)), jnp.ones((64,)))
    with pytest.raises(ValueError, match="chunk"):
        ops.ssd_scan(jnp.ones((1, 96, 1, 8)), jnp.ones((1, 96, 1)),
                     -jnp.ones((1,)), jnp.ones((1, 96, 4)),
                     jnp.ones((1, 96, 4)), chunk=20)


def test_model_level_pallas_path_matches_xla():
    """StackModel forward with pallas-interpret attention == XLA path."""
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.models.attention import set_attention_impl

    cfg = reduced_config(get_config("qwen2-0.5b"))
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch = {"tokens": jnp.arange(2 * 64, dtype=jnp.int32).reshape(2, 64) % 100,
             "labels": jnp.ones((2, 64), jnp.int32)}
    logits_xla, _ = m.forward(params, batch)
    try:
        set_attention_impl("pallas_interpret")
        logits_pl, _ = m.forward(params, batch)
    finally:
        set_attention_impl("xla")
    np.testing.assert_allclose(np.asarray(logits_xla), np.asarray(logits_pl),
                               atol=2e-3, rtol=2e-3)
