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


def _attention_ref_gqa(q, k, v, causal=True):
    """``ref.attention_ref`` in model layout, K/V repeated over each group."""
    b, s, h, _ = q.shape
    flat = lambda t: jnp.moveaxis(jnp.repeat(t, h // t.shape[2], axis=2), 2,
                                  1).reshape(b * h, s, t.shape[-1])
    o = ref.attention_ref(flat(q), flat(k), flat(v), causal)
    return jnp.moveaxis(o.reshape(b, h, s, v.shape[-1]), 1, 2)


@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("s,blocks", [(128, None), (512, (128, 256))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grads_match_reference(group, hd, s, blocks, dtype):
    """Forward and the kernels' own backward against the f32 reference,
    with grouped K/V that are never expanded."""
    kv = 2 if group == 7 else 1
    q = _rand(1, (1, s, kv * group, hd), dtype)
    k = _rand(2, (1, s, kv, hd), dtype)
    v = _rand(3, (1, s, kv, hd), dtype)
    do = _rand(4, (1, s, kv * group, hd), dtype)
    f32 = lambda t: t.astype(jnp.float32)

    def out_and_grads(attn):
        def f(q, k, v):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o, *vjp(do.astype(o.dtype)))
        return jax.jit(f)

    kernel = lambda q, k, v: ops.flash_attention(q, k, v, blocks=blocks,
                                                 interpret=True)
    got = out_and_grads(kernel)(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(_attention_ref_gqa)(f32(q), f32(k), f32(v))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.dtype == dtype, name
        err = float(jnp.max(jnp.abs(f32(g) - w)) / jnp.max(jnp.abs(w)))
        assert err <= tol, (name, err)


@pytest.mark.parametrize("dqk,dv", [(48, 32), (192, 128)])
@pytest.mark.parametrize("group,s", [(1, 128), (2, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_value_width_differs(dqk, dv, group, s, dtype):
    """Latent attention's shapes: q/k heads wider than v heads (192/128 as
    published, 48/32 reduced). Forward and dQ, dK, dV against the f32
    reference, scaled by 1/sqrt(d_qk)."""
    q = _rand(1, (1, s, 2 * group, dqk), dtype)
    k = _rand(2, (1, s, 2, dqk), dtype)
    v = _rand(3, (1, s, 2, dv), dtype)
    do = _rand(4, (1, s, 2 * group, dv), dtype)
    f32 = lambda t: t.astype(jnp.float32)

    def out_and_grads(attn):
        def f(q, k, v):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o, *vjp(do.astype(o.dtype)))
        return jax.jit(f)

    kernel = lambda q, k, v: ops.flash_attention(
        q, k, v, blocks=(64, 64) if s > 128 else None, interpret=True)
    got = out_and_grads(kernel)(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(_attention_ref_gqa)(f32(q), f32(k), f32(v))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == dtype, name
        err = float(jnp.max(jnp.abs(f32(g) - w)) / jnp.max(jnp.abs(w)))
        assert err <= tol, (name, err)


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
    from repro.models.attention import attention_impl

    cfg = reduced_config(get_config("qwen2-0.5b"))
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch = {"tokens": jnp.arange(2 * 64, dtype=jnp.int32).reshape(2, 64) % 100,
             "labels": jnp.ones((2, 64), jnp.int32)}
    logits_xla, _ = m.forward(params, batch)
    with attention_impl("pallas_interpret"):
        logits_pl, _ = m.forward(params, batch)
    np.testing.assert_allclose(np.asarray(logits_xla), np.asarray(logits_pl),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("dtype,remat", [("float32", "none"),
                                         ("bfloat16", "full")])
def test_train_step_pallas_path_matches_xla(dtype, remat):
    """The reduced-width qwen2 train step, kernel against XLA: the loss and
    every weight's gradient norm."""
    import dataclasses

    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.models.attention import TRACES, attention_impl

    cfg = dataclasses.replace(reduced_config(get_config("qwen2-0.5b"), dtype=dtype),
                              remat=remat)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def readings(impl):
        with attention_impl(impl):
            # a new function per pin: JAX caches a trace by function
            grad = jax.jit(jax.value_and_grad(lambda p: m.loss(p, batch)[0]))
            loss, g = grad(params)
        norms = jax.tree.map(lambda x: float(jnp.linalg.norm(x.astype(jnp.float32))), g)
        return float(loss), norms

    flash = TRACES.counter("flash").value
    loss_x, norms_x = readings("xla")
    assert TRACES.counter("flash").value == flash
    loss_p, norms_p = readings("pallas_interpret")
    assert TRACES.counter("flash").value > flash
    tol = 2e-2 if dtype == "bfloat16" else 1e-4
    assert loss_p == pytest.approx(loss_x, rel=tol)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(norms_x),
                            jax.tree.leaves(norms_p)):
        assert b == pytest.approx(a, rel=tol, abs=1e-6), path


def test_auto_takes_xla_on_the_cpu():
    from repro.configs import get_config
    from repro.models import attention as A

    assert A.get_attention_impl() == "auto" and jax.default_backend() == "cpu"
    q = jax.ShapeDtypeStruct((1, 2048, 14, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16)
    assert A._flash_blocks(q, k, None) is None
    pos = jnp.arange(2048)[None, :]
    attn = lambda q, k, v: A.causal_attention(get_config("qwen2-0.5b"), q, k, v, pos)
    assert "pallas_call" not in str(jax.make_jaxpr(attn)(q, k, k))
    assert A.attention_key() == "xla"


@pytest.mark.parametrize("sq,sk,hd,takes", [
    (2048, 2048, 64, True), (4096, 4096, 128, True),
    (2048, 1024, 64, False),   # not square
    (1152, 1152, 64, True), (1100, 1100, 64, False),  # 128 tiles 1152 only
    (2048, 2048, 80, False),   # a head width the kernel is not built for
    (8192, 8192, 192, True),   # latent attention's q/k heads
    (256, 256, 64, False),     # under FLASH_MIN_SEQ
])
def test_auto_shape_rule_on_a_tpu(monkeypatch, sq, sk, hd, takes):
    from repro.models import attention as A

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, sq, 4, hd), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, sk, 2, hd), jnp.bfloat16)
    blocks = A._flash_blocks(q, k, None)
    assert (blocks is not None) == (takes and sq >= A.FLASH_MIN_SEQ)
    if blocks is not None:
        assert sq % blocks[0] == 0 and sk % blocks[1] == 0
    # a sharded model keeps the XLA path, which GSPMD can partition
    assert A._flash_blocks(q, k, lambda name, shape: None) is None
    assert A.attention_key() == f"flash>={A.FLASH_MIN_SEQ}"


def test_pinned_settings():
    from repro.models import attention as A

    q = jax.ShapeDtypeStruct((1, 64, 4, 16), jnp.float32)
    with A.attention_impl("pallas_interpret"):
        assert A._flash_blocks(q, q, None) == (64, 64)
        assert A.attention_key() == "pallas_interpret"
    with A.attention_impl("xla"):
        assert A._flash_blocks(q, q, None) is None
    assert A.get_attention_impl() == "auto"
    with pytest.raises(ValueError, match="not in"):
        A.set_attention_impl("triton")
    with pytest.raises(ValueError, match="tile"):
        ops.flash_blocks(1100, 64)


def test_profile_features_do_not_depend_on_the_kernel():
    """NSM edges and FLOPs of the profiled step equal under the kernel and
    XLA, and equal the digest and count pinned before the kernel existed."""
    import hashlib
    import json

    from repro.configs import get_config, reduced_config
    from repro.core import profiler
    from repro.models.attention import attention_impl

    cfg = reduced_config(get_config("qwen2-0.5b"))
    recs = {}
    for impl in ("xla", "pallas_interpret"):
        with attention_impl(impl):
            recs[impl] = profiler.profile_lm(cfg, batch=2, seq=32, steps=1)
    x, p = recs["xla"], recs["pallas_interpret"]
    assert x.nsm_edges == p.nsm_edges and x.flops == p.flops
    edges = json.dumps(sorted((f"{a}->{b}", v) for (a, b), v in x.nsm_edges.items()))
    assert hashlib.sha256(edges.encode()).hexdigest() == NSM_DIGEST
    assert x.flops == PROFILE_FLOPS


# profile_lm(reduced qwen2-0.5b, batch 2, seq 32) before the kernel had a
# backward pass: its NSM digest (test_train_tracing's) and HLO FLOPs
NSM_DIGEST = "bbc45e031f7cf47157d3522410060fc5e70077f89999345ed674c295758c45f1"
PROFILE_FLOPS = 50101175.0
