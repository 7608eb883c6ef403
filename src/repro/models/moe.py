"""Mixture-of-Experts layers, chosen by the router's scoring function.

``softmax`` (Arctic, Jamba): grouped one-hot (GSPMD-style) dispatch.
Tokens are routed with capacity-bounded one-hot dispatch/combine einsums,
which the XLA partitioner turns into all-to-alls on the ``model`` axis.
Tokens are split into groups of ``cfg.moe_group_size`` so the dispatch
tensor stays ``tokens × (group·k·capacity_factor)`` elements,
independent of E; tokens past an expert's capacity are dropped.

``sigmoid`` (DeepSeek-V3's ``noaux_tc``): dropless, for a device that
holds the first ``cfg.held_experts`` experts of the router's columns, as
expert parallelism gives each chip its share. Every token is routed over
all experts; the (token, choice) pairs that name a held expert are sorted by
expert and run through grouped matmuls whose work follows the rows each
expert got (nothing is dropped, shapes stay static); the pairs of absent
experts are left out, as their chips would compute them; the shared
experts add their part once. Named scopes ``router``, ``permute``,
``experts`` and ``shared`` split the layer's ``moe`` scope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention
from repro.models import layers as L
from repro.models.module import spec
from repro.obs import events
from repro.obs.metrics import MetricsRegistry

AUX_LOSS_WEIGHT = 0.01
# traces of the expert layer by implementation (``capacity``, ``dropless``)
TRACES = MetricsRegistry(namespace="moe")


def moe_spec(cfg):
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    held = cfg.held_experts
    p = {
        "router": spec((d, e), ("embed", None), "small", dtype=jnp.float32),
        "wi": spec((held, d, 2, f), ("experts", "embed", None, "mlp")),
        "wo": spec((held, f, d), ("experts", "mlp", "embed")),
    }
    if cfg.moe_scoring == "sigmoid":
        # the selection bias of noaux_tc: it picks experts and weights none;
        # a balancing rule outside the gradient moves it, so it starts at 0
        p["router_bias"] = spec((e,), (None,), "zeros", dtype=jnp.float32)
    if cfg.shared_experts:
        p["shared"] = L.mlp_spec(cfg, cfg.shared_experts * f)
    return p


def capacity(cfg, group_size: int) -> int:
    c = int(group_size * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4, >= 4


def _constrain(shard, name, x):
    if shard is None:
        return x
    sh = shard(name, x.shape)
    import jax.lax
    return jax.lax.with_sharding_constraint(x, sh) if sh is not None else x


@jax.named_scope("moe")
def apply_moe(p, cfg, x, shard=None):
    """x (B,S,D) -> (y (B,S,D), aux_loss scalar fp32)."""
    impl = "dropless" if cfg.moe_scoring == "sigmoid" else "capacity"
    TRACES.counter(impl, f"expert layer traced on the {impl} path").inc()
    events.emit("moe_impl", impl=impl, tokens=x.shape[0] * x.shape[1],
                experts=cfg.num_experts, experts_held=cfg.held_experts)
    if impl == "dropless":
        return _dropless(p, cfg, x), jnp.zeros((), jnp.float32)
    if cfg.held_experts != cfg.num_experts:
        raise ValueError("the capacity-bounded layer holds every expert")
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    gs = min(cfg.moe_group_size, b * s)
    tokens = b * s
    xt = x.reshape(tokens, d)
    if tokens % gs:  # pad to a group multiple; padded rows are sliced off
        pad = gs - tokens % gs
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    g = xt.shape[0] // gs
    xg = xt.reshape(g, gs, d)

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)  # (G,gs,E) fp32

    top_vals, top_idx = jax.lax.top_k(gates, k)  # (G,gs,k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    c = capacity(cfg, gs)
    eh = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (G,gs,k,E)
    # Priority order: token-major, then choice rank.
    ehf = eh.reshape(g, gs * k, e)
    pos = jnp.cumsum(ehf, axis=1) - ehf  # (G,gs*k,E) slot within expert
    keep = (pos < c).astype(jnp.float32) * ehf
    disp = keep[..., None] * jax.nn.one_hot(pos.astype(jnp.int32), c,
                                            dtype=jnp.float32)  # (G,gs*k,E,C)
    comb = disp * top_vals.reshape(g, gs * k)[..., None, None]
    # Fold the k choices back onto tokens (each (token,expert) pair unique).
    disp4 = disp.reshape(g, gs, k, e, c).sum(axis=2).astype(x.dtype)
    comb4 = comb.reshape(g, gs, k, e, c).sum(axis=2).astype(x.dtype)
    disp4 = _constrain(shard, "moe_disp", disp4)
    comb4 = _constrain(shard, "moe_disp", comb4)

    xe = jnp.einsum("gsec,gsd->gecd", disp4, xg)  # (G,E,C,D) dispatch
    xe = _constrain(shard, "moe_xe", xe)
    hi = jnp.einsum("gecd,ednf->gecnf", xe, p["wi"].astype(x.dtype))
    h = jax.nn.silu(hi[..., 0, :].astype(jnp.float32)).astype(x.dtype) * hi[..., 1, :]
    ye = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(x.dtype))
    ye = _constrain(shard, "moe_xe", ye)
    y = jnp.einsum("gecd,gsec->gsd", ye, comb4)  # combine
    y = y.reshape(-1, d)[:tokens]

    # Load-balance auxiliary loss (Switch-style): E * sum(frac_tokens * frac_prob)
    frac_tokens = jnp.mean(eh.sum(axis=2), axis=(0, 1))  # (E,)
    frac_prob = jnp.mean(gates, axis=(0, 1))  # (E,)
    aux = e * jnp.sum(frac_tokens * frac_prob) * AUX_LOSS_WEIGHT

    return y.reshape(b, s, d), aux


def route(p, cfg, xt):
    """noaux_tc routing of tokens xt (T, D): fp32 sigmoid scores over all
    experts; the top k of score + selection bias; the chosen scores without
    the bias, normalised to sum 1 and scaled by ``routed_scaling``.
    -> (expert ids (T, k), weights (T, k) fp32)."""
    logits = jnp.dot(xt.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["router_bias"])
    _, idx = jax.lax.top_k(scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, rows, inv, k):
    """xt[rows]: each pair's token row, in expert order. The transpose is a
    gather too: back in pair order (``inv``), each token sums its k rows."""
    return jnp.take(xt, rows, axis=0)


def _dispatch_fwd(xt, rows, inv, k):
    return _dispatch(xt, rows, inv, k), inv


def _dispatch_bwd(k, inv, g):
    gt = jnp.take(g, inv, axis=0).reshape(-1, k, g.shape[-1])
    return gt.sum(axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, idx, inv):
    """x[idx] for a permutation ``idx`` whose inverse is ``inv``; the
    transpose gathers by ``inv`` (no scatter)."""
    return jnp.take(x, idx, axis=0)


def _permute_fwd(x, idx, inv):
    return _permute(x, idx, inv), inv


def _permute_bwd(inv, g):
    return jnp.take(g, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _grouped(lhs, rhs, sizes):
    """Rows of ``lhs`` in consecutive groups of ``sizes`` times their
    group's matrix of ``rhs`` (G, K, N); rows past the groups are not
    defined (the caller masks them). The kernel paths' one pin
    (``attention.attention_impl``) chooses: ``auto`` takes the megablox
    kernel on a TPU, where it beat ``jax.lax.ragged_dot`` (PERF.md), and
    ragged_dot elsewhere; ``xla`` is ragged_dot, ``pallas`` and
    ``pallas_interpret`` the kernel."""
    impl = attention.get_attention_impl()
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    from repro.kernels import ops as kops
    return kops.grouped_matmul(lhs, rhs, sizes,
                               interpret=impl == "pallas_interpret")


def _dropless(p, cfg, x):
    b, s, d = x.shape
    k, held, f = cfg.top_k, cfg.held_experts, cfg.expert_d_ff
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    with jax.named_scope("router"):
        idx, w = route(p, cfg, xt)
    with jax.named_scope("permute"):
        # (token, choice) pairs, token-major; an absent expert's sort last
        group = jnp.minimum(idx.reshape(-1), held)
        order = jnp.argsort(group, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
        sizes = jnp.sum(group[:, None] == jnp.arange(held), axis=0,
                        dtype=jnp.int32)
        routed = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
        xs = jnp.where(routed, _dispatch(xt, order // k, inv, k), 0)
    with jax.named_scope("experts"):
        gu = _grouped(xs, p["wi"].reshape(held, d, 2 * f).astype(x.dtype), sizes)
        h = jax.nn.silu(gu[:, :f].astype(jnp.float32)).astype(x.dtype) * gu[:, f:]
        ys = jnp.where(routed, _grouped(h, p["wo"].astype(x.dtype), sizes), 0)
    with jax.named_scope("permute"):
        yk = _permute(ys, inv, order).reshape(t, k, d)
        y = jnp.sum(yk.astype(jnp.float32) * w[..., None], axis=1)
    if cfg.shared_experts:
        with jax.named_scope("shared"):
            y = y + L.swiglu(p["shared"], xt)
    return y.astype(x.dtype).reshape(b, s, d)
