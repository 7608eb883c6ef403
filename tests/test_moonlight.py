"""Moonlight-16B-A3B's block at reduced widths on the CPU: latent attention
(MLA), the dropless sigmoid-routed expert layer that holds a share of the
experts, and the leading dense layer ahead of the layer scan, each against
a plain computation on seeded random weights."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import attention as A
from repro.models import build_model
from repro.models import layers as L
from repro.models import moe
from repro.models.module import init_params
from repro.obs import events


def _cfg(**kw):
    return dataclasses.replace(reduced_config(get_config("moonlight-16b-a3b")), **kw)


def _rand(seed, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.key(seed), shape, jnp.float32)


# --- latent attention -------------------------------------------------------

def _plain_mla(p, cfg, x):
    """MLA as DeepSeek-V2/V3 write it: full softmax with a causal mask,
    RoPE (rotate-half) on the rope dims of q and of the one shared key."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pos = jnp.arange(s, dtype=jnp.float32)
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    cos, sin = jnp.cos(pos[:, None] * inv), jnp.sin(pos[:, None] * inv)

    def rope(t):  # (b, s, heads, dr)
        t1, t2 = t[..., : dr // 2], t[..., dr // 2:]
        c, s_ = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([t1 * c - t2 * s_, t2 * c + t1 * s_], axis=-1)

    q = (x @ p["wq"]).reshape(b, s, h, dn + dr)
    ckv = x @ p["wkv_a"]
    c = ckv[..., :r]
    c = c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + cfg.norm_eps)
    kv = ((c * p["kv_norm"]["scale"]) @ p["wkv_b"]).reshape(b, s, h, dn + dv)
    q_pe, k_pe = rope(q[..., dn:]), rope(ckv[:, :, None, r:])
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
              + jnp.einsum("bqhd,bkhd->bhqk", q_pe, jnp.broadcast_to(
                  k_pe, (b, s, h, dr)))) / np.sqrt(dn + dr)
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(b, s, h * dv)
    return ctx @ p["wo"]


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_mla_matches_a_plain_reference(impl):
    """Forward and the gradients of x and every weight, on the XLA path and
    through the flash kernel (q/k 24 wide, v 16)."""
    cfg = _cfg()
    p = init_params(A.attn_spec(cfg), jax.random.key(0), jnp.float32)
    assert set(p) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    p["kv_norm"]["scale"] = 1.0 + _rand(9, p["kv_norm"]["scale"].shape, 0.1)
    x = _rand(1, (2, 128, cfg.d_model))
    dy = _rand(2, (2, 128, cfg.d_model))
    pos = jnp.arange(128, dtype=jnp.int32)[None, :]
    with A.attention_impl(impl):
        def f(p, x):
            return jnp.sum(A.apply_self_attn(p, cfg, x, pos)[0] * dy)
        got, g_got = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, x)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(
            lambda p, x: jnp.sum(_plain_mla(p, cfg, x) * dy), argnums=(0, 1))(p, x)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for path, a in jax.tree_util.tree_leaves_with_path(g_got):
        b = g_want
        for k in path:
            b = b[k.idx] if hasattr(k, "idx") else b[k.key]
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < 1e-4, (path, err)


def test_mla_prefill_then_decode_matches_the_forward():
    """The per-head cache holds k 24 and v 16 wide; decoding the next token
    gives the forward pass's logits."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(3))
    shapes, _ = model.cache_spec(2, 16)
    assert shapes["L0"]["k"].shape[-1] == 24 and shapes["L0"]["v"].shape[-1] == 16
    assert "dense_layers" in shapes
    tokens = jax.random.randint(jax.random.key(4), (2, 17), 0, cfg.vocab_size)
    full, _ = model.forward(params, {"tokens": tokens})
    _, cache = model.prefill(params, {"tokens": tokens[:, :16]})
    cache = jax.tree.map(lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, 1)]
                                           + [(0, 0)] * (a.ndim - 3)), cache)
    logits, _ = model.decode_step(params, cache, tokens[:, 16:],
                                  jnp.full((2,), 16, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, -1]),
                               atol=2e-4, rtol=2e-4)


# --- the router and the dropless expert layer --------------------------------

def test_router_math():
    """Sigmoid scores; the bias picks and does not weight; the chosen scores
    are normalised to 1 and scaled by routed_scaling."""
    cfg = _cfg(num_experts=4, top_k=2, routed_scaling=2.446)
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 3.0, 1.0]])
    # x @ router == logits, with x the first two unit rows
    p = {"router": jnp.zeros((cfg.d_model, 4)).at[0].set(logits[0]).at[1].set(logits[1]),
         "router_bias": jnp.array([0.0, 0.0, 5.0, 0.0])}
    x = jnp.zeros((2, cfg.d_model)).at[0, 0].set(1.0).at[1, 1].set(1.0)
    idx, w = moe.route(p, cfg, x)
    sig = jax.nn.sigmoid(logits)
    # token 0: scores 0.88, 0.73, 0.5, 0.27; the bias lifts expert 2 first
    assert idx.tolist() == [[2, 0], [2, 3]]
    for t in range(2):
        chosen = sig[t, idx[t]]
        np.testing.assert_allclose(np.asarray(w[t]),
                                   np.asarray(chosen / chosen.sum() * 2.446), rtol=1e-6)
    assert float(w.sum(axis=1).mean()) == pytest.approx(2.446, rel=1e-6)
    # no gradient reaches the selection bias
    g = jax.grad(lambda b: moe.route({**p, "router_bias": b}, cfg, x)[1].sum())(
        p["router_bias"])
    assert float(jnp.max(jnp.abs(g))) == 0.0


def _moe_params(cfg, seed=0):
    spec = moe.moe_spec(cfg)
    p = init_params(spec, jax.random.key(seed), jnp.float32)
    p["router"] = _rand(seed + 1, p["router"].shape, 0.3)
    return p


def _plain_moe(p, cfg, x):
    """Per token, its chosen experts that are held here, each SwiGLU weighted
    by its gate, plus the shared experts: a loop, nothing dropped."""
    xt = x.reshape(-1, x.shape[-1])
    idx, w = moe.route(p, cfg, xt)
    y = []
    for t in range(xt.shape[0]):
        acc = L.swiglu(p["shared"], xt[t])
        for j in range(cfg.top_k):
            e = int(idx[t, j])
            if e < cfg.held_experts:
                acc = acc + w[t, j] * L.swiglu({"wi": p["wi"][e], "wo": p["wo"][e]}, xt[t])
        y.append(acc)
    return jnp.stack(y).reshape(x.shape)


@pytest.mark.parametrize("bias", [
    [8.0, 0.0, 0.0, 0.0],   # every token to held expert 0 (and one other)
    [8.0, 8.0, 0.0, 0.0],   # every choice of every token to the held experts
])
def test_dropless_under_full_imbalance(bias):
    cfg = _cfg(experts_held=2)
    p = _moe_params(cfg)
    p["router_bias"] = jnp.array(bias)
    x = _rand(3, (2, 32, cfg.d_model))
    y, aux = jax.jit(lambda p, x: moe.apply_moe(p, cfg, x))(p, x)
    idx, _ = moe.route(p, cfg, x.reshape(-1, cfg.d_model))
    assert bool(jnp.all(jnp.any(idx == 0, axis=1)))
    assert float(aux) == 0.0
    with jax.default_matmul_precision("highest"):
        want = _plain_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_the_tpu_kernel_path_matches_and_masks_unwritten_rows(monkeypatch):
    """On a TPU the held experts run through the megablox kernel (here in
    interpret mode), whose rows past the groups are never written: filled
    with NaN they change nothing in the output or any gradient."""
    from repro.kernels import ops as kops

    cfg = _cfg(experts_held=2)
    p = _moe_params(cfg, seed=11)
    x = _rand(12, (2, 32, cfg.d_model))
    loss = lambda p, x: jnp.sum(moe.apply_moe(p, cfg, x)[0] ** 2)
    with A.attention_impl("xla"):
        want, g_want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)

    kernel = kops.grouped_matmul

    def unwritten_nan(lhs, rhs, sizes, interpret):
        assert interpret
        out = kernel(lhs, rhs, sizes, interpret=True)
        return jnp.where((jnp.arange(lhs.shape[0]) < sizes.sum())[:, None], out, jnp.nan)

    monkeypatch.setattr(kops, "grouped_matmul", unwritten_nan)
    with A.attention_impl("pallas_interpret"):
        got, g_got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


def test_shares_add_up_to_the_uncut_layer():
    """Two chips of two experts each: their routed parts, with the shared
    experts counted once, are the layer that holds all four. A chip holds
    the first experts of its router's columns, so the second chip's router
    has the columns (and the selection bias) rolled by two: a permutation
    of the experts, which moves no top-k choice and no weight."""
    cfg = _cfg()
    assert cfg.held_experts == cfg.num_experts == 4
    p = _moe_params(cfg, seed=5)
    p["router_bias"] = jnp.array([0.0, 0.01, -0.02, 0.03])
    x = _rand(6, (2, 24, cfg.d_model))
    whole, _ = moe.apply_moe(p, cfg, x)
    share_cfg = dataclasses.replace(cfg, experts_held=2)
    parts = []
    for first in (0, 2):
        ps = dict(p, router=jnp.roll(p["router"], -first, axis=1),
                  router_bias=jnp.roll(p["router_bias"], -first),
                  wi=p["wi"][first:first + 2], wo=p["wo"][first:first + 2])
        parts.append(moe.apply_moe(ps, share_cfg, x)[0])
    shared = L.swiglu(p["shared"], x)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared),
                               np.asarray(whole), atol=2e-5, rtol=2e-5)
    grads = jax.grad(lambda p: jnp.sum(moe.apply_moe(p, cfg, x)[0] ** 2))(p)
    assert float(jnp.abs(grads["router_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["router"]).max()) > 0.0


def test_the_dropless_path_is_counted_and_logged():
    before = moe.TRACES.counter("dropless").value
    cap = moe.TRACES.counter("capacity").value
    cfg = _cfg(experts_held=2)
    jax.eval_shape(lambda p, x: moe.apply_moe(p, cfg, x),
                   _moe_params(cfg), jnp.zeros((2, 8, cfg.d_model)))
    assert moe.TRACES.counter("dropless").value == before + 1
    assert moe.TRACES.counter("capacity").value == cap
    ev = [e for e in events.tail() if e["event"] == "moe_impl"][-1]
    assert (ev["impl"], ev["tokens"], ev["experts"], ev["experts_held"]) == (
        "dropless", 16, 4, 2)
    jamba = reduced_config(get_config("jamba-v0.1-52b"))
    jax.eval_shape(lambda p, x: moe.apply_moe(p, jamba, x),
                   init_params(moe.moe_spec(jamba), jax.random.key(0)),
                   jnp.zeros((2, 8, jamba.d_model)))
    assert moe.TRACES.counter("capacity").value == cap + 1


# --- the stack -----------------------------------------------------------------

def test_leading_dense_layer_then_scan_equals_a_plain_loop():
    cfg = _cfg(remat="full")
    assert cfg.first_dense_layers == 1 and cfg.pattern() == [("attn", "moe")]
    assert cfg.layer_kind(0) == ("attn", "dense") and cfg.num_periods == 2
    model = build_model(cfg)
    params = model.init(jax.random.key(7))
    assert params["dense_layers"]["L0"]["mlp"]["wi"].shape[-1] == cfg.d_ff
    tokens = jax.random.randint(jax.random.key(8), (2, 32), 0, cfg.vocab_size)
    logits, _ = jax.jit(model.forward)(params, {"tokens": tokens})

    x = L.embed_tokens(params["embed"], tokens, jnp.float32)
    pos = jnp.arange(32, dtype=jnp.int32)[None, :]
    layers = [jax.tree.map(lambda a: a[0], params["dense_layers"]["L0"])]
    layers += [jax.tree.map(lambda a, i=i: a[i], params["layers"]["L0"])
               for i in range(cfg.num_periods)]
    for i, p in enumerate(layers):
        x, _ = model._apply_layer(cfg.layer_kind(i), p, x, pos, None)
    x = L.apply_norm(params["final_norm"], x, cfg.norm_eps)
    want = L.unembed(params["embed"], x, 0.0, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_norm_eps_reaches_every_norm(monkeypatch):
    """Every RMSNorm of the train step, prefill and decode (the latent's
    among them) takes the configuration's eps; qwen2 keeps 1e-6."""
    seen = []
    real = L.apply_norm

    def spy(p, x, eps=1e-6):
        seen.append(eps)
        return real(p, x, eps)

    monkeypatch.setattr(L, "apply_norm", spy)
    cfg = _cfg(norm_eps=0.25)
    model = build_model(cfg)
    params = model.init_shape()
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    jax.eval_shape(lambda p, t: model.loss(p, {"tokens": t, "labels": t}), params, tokens)
    jax.eval_shape(lambda p, t: model.prefill(p, {"tokens": t}), params, tokens)
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    jax.eval_shape(model.decode_step, params, cache,
                   jax.ShapeDtypeStruct((2, 1), jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.int32))
    # each scan body is traced once: the dense layer's and the period's
    # norm1, latent norm and norm2; then the final norm
    per_pass = 3 * (cfg.first_dense_layers + cfg.period) + 1
    assert len(seen) == 3 * per_pass and set(seen) == {0.25}
    seen.clear()
    q = reduced_config(get_config("qwen2-0.5b"))
    jax.eval_shape(lambda p, t: build_model(q).loss(p, {"tokens": t, "labels": t}),
                   build_model(q).init_shape(), tokens)
    assert seen and set(seen) == {1e-6}


def test_moe_scopes_split_the_layer_and_hold_no_mlp():
    """The train step's ops carry ``moe`` with ``router``, ``permute``,
    ``experts`` and ``shared`` inside; no op of the expert layer carries
    ``mlp``, which the leading dense layer's ops do."""
    from repro.train import optimizer as opt_lib
    from repro.train import step as step_lib

    model = build_model(_cfg(dtype="bfloat16", remat="full", experts_held=2))
    opt = opt_lib.OptConfig()
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    text = jax.jit(step_lib.make_train_step(model, opt)).lower(
        step_lib.state_shapes(model, opt), batch).as_text(dialect="hlo", debug_info=True)
    paths = [set(re.findall(r"[^/()]+", n)) for n in re.findall(r'op_name="([^"]*)"', text)]
    in_moe = [p for p in paths if "moe" in p]
    for scope in ("router", "permute", "experts", "shared"):
        assert any(scope in p for p in in_moe), scope
        assert all("moe" in p for p in paths if scope in p), scope
    assert not any("mlp" in p for p in in_moe)
    assert any("mlp" in p for p in paths)
    # the permutation's backward gathers too: its one scatter writes the
    # inverse of the sort order (int32), none sums rows of activations
    scatters = [line.split(" = ", 1)[1].split(" ", 1)[0]
                for line in text.splitlines()
                if " scatter(" in line and "moe/permute" in line]
    assert scatters and all(t.startswith("s32[") for t in scatters), scatters
