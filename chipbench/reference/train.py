"""Plain AdamW training of the first steps, in blocks of rows.

AdamW as stated in a traffic file's ``optimizer``: the gradient is
clipped to a global norm of ``grad_clip``; moments
``m = b1 m + (1 - b1) g`` and ``v = b2 v + (1 - b2) g^2``, bias-corrected
by ``1 - b^t``; the update ``lr_t (m_hat / (sqrt(v_hat) + eps) + wd w)``
is applied to every weight. ``lr_t`` warms up linearly over
``warmup_steps`` (``lr (t + 1) / warmup``) and then follows a cosine to
``min_lr_ratio * lr`` at ``decay_steps``.

Memory: the weights, the moments and one gradient accumulator in fp32,
and the activations of ``rows_per_block`` rows at a time (each layer is
recomputed in the backward pass). The loss over a batch is the mean of
its blocks' losses, as every row has the same number of tokens.

Readings, as the benchmark compares them:

- ``loss``: each step's loss;
- ``grad``: per weight, the norm of the first step's clipped gradient;
- ``change``: per weight, the norm of (weights after the last step -
  initial weights).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench import registry
from chipbench import weights as W
from chipbench.reference.precision import DOTS


def lr_at(opt: Dict[str, Any], step: int) -> float:
    warm = min(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    span = max(1, opt["decay_steps"] - opt["warmup_steps"])
    prog = min(1.0, max(0.0, (step - opt["warmup_steps"]) / span))
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in W.flatten(tree).items()}


def change_norms(specs: W.Spec, key, tree) -> Dict[str, jax.Array]:
    """Per leaf, ||tree - initial weights||; the initial leaf is made again."""
    flat = W.flatten(tree)
    return {p: jnp.sqrt(jnp.sum(jnp.square(
        flat[p].astype(jnp.float32) - W.make_leaf(specs, key, p, jnp.float32))))
        for p in flat}


def run(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
        precision: str = "highest", steps: int = 3,
        rows_used: int = 0) -> Dict[str, Any]:
    model = registry.reference(cfg)
    dot = DOTS[precision]
    opt = traffic["optimizer"]
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], opt["weight_decay"]
    specs = W.leaf_specs(cfg)
    key = W.seed_key(seed)
    data = W.tokens(cfg, traffic, seed)
    rows = traffic["ref_rows_per_block"]
    nblk = (rows_used or traffic["batch"]) // rows  # rows_used: a planted fault

    params = jax.jit(lambda k: W.make_params(specs, k, jnp.float32))(key)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)

    def block(params, acc, tokens, labels):
        val, g = jax.value_and_grad(model.loss)(params, tokens, labels, cfg, dot)
        return val, jax.tree.map(lambda a, x: a + x / nblk, acc, g)

    block = jax.jit(block, donate_argnums=(1,))

    def adam(params, g, m, v, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
        g = jax.tree.map(lambda x: x * clip, g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda w, a, s: w - lr * ((a / c1) / (jnp.sqrt(s / c2) + eps) + wd * w),
            params, m, v)
        return params, m, v, leaf_norms(g)

    adam = jax.jit(adam, donate_argnums=(0, 1, 2, 3))

    losses: List[float] = []
    grad = None
    for t in range(steps):
        batch = data.batch_at(t)
        acc, total = zeros(params), 0.0
        for r in range(nblk):
            sl = slice(r * rows, (r + 1) * rows)
            val, acc = block(params, acc, batch["tokens"][sl], batch["labels"][sl])
            total += float(val)
        losses.append(total / nblk)
        params, m, v, gnorms = adam(params, acc, m, v, float(t + 1),
                                    lr_at(opt, t))
        if t == 0:
            grad = {p: float(x) for p, x in gnorms.items()}
    change = jax.jit(lambda k, t: change_norms(specs, k, t))(key, params)
    return {"loss": losses, "grad": grad,
            "change": {p: float(x) for p, x in change.items()}}
