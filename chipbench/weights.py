"""Weights and tokens made from ``--seed``, on the device, in one call.

The weights are laid out as the program stores them, as the
configuration's reference module states it (``leaf_specs``), and the
plain reference reads the same tree. Each leaf is drawn
from its own key, ``fold_in(seed_key, leaf_index)``, so one leaf can be
made again alone. Values are rounded to the dtype the configuration
serves them in; the reference computes on those rounded values in fp32.

Tokens follow the law the traffic file states (``Tokens``): every seed
draws different rows of the same sizes, and a row never repeats another.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import registry

# (shape, dtype, init) per leaf path; init is ("normal", std) or
# ("const", value).
Spec = Dict[str, Tuple[Tuple[int, ...], Any, tuple]]

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def seed_key(seed: int) -> jax.Array:
    """A key that uses all bits of a seed that may be wider than 32."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_specs(cfg: Dict[str, Any]) -> Spec:
    """The program's weight layout, from the configuration's reference module."""
    return registry.reference(cfg).leaf_specs(cfg)


def _draw(key, shape, init) -> jax.Array:
    if init[0] == "const":
        return jnp.full(shape, init[1], jnp.float32)
    if init[0] == "normal":
        return init[1] * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(init)


def make_leaf(specs: Spec, key, path: str, dtype=None) -> jax.Array:
    """One leaf, as the configuration serves it (or cast to ``dtype``)."""
    i = sorted(specs).index(path)
    shape, dt, init = specs[path]
    x = _draw(jax.random.fold_in(key, i), shape, init).astype(dt)
    return x if dtype is None else x.astype(dtype)


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, x in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


def make_params(specs: Spec, key, dtype=None) -> Dict[str, Any]:
    """The whole tree; call it under ``jax.jit``."""
    return nest({p: make_leaf(specs, key, p, dtype) for p in specs})


class Tokens:
    """Rows of token ids; ``batch_at(step)`` is seeded by (seed, step).

    ``law`` is ``"zipf"`` (Zipf(``a``) over the vocabulary, the arithmetic
    of the program's ``SyntheticLM``: a few ids fill most positions, as in
    natural text) or ``"uniform"`` (every id equally likely, so a step's
    rows name few ids twice).
    """

    def __init__(self, vocab_size: int, batch: int, seq: int, seed: int,
                 law: str = "zipf", a: float = 1.3):
        if law not in ("zipf", "uniform"):
            raise ValueError(f"unknown token law {law!r}")
        self.vocab_size, self.batch, self.seq = vocab_size, batch, seq
        self.seed, self.law, self.a = seed, law, a

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        shape = (self.batch, self.seq + 1)
        if self.law == "uniform":
            tokens = rng.integers(0, self.vocab_size, size=shape, dtype=np.int32)
        else:
            z = rng.zipf(self.a, size=shape)
            tokens = np.minimum(z - 1, self.vocab_size - 1).astype(np.int32)
        return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}


def tokens(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> Tokens:
    """The token rows a traffic file states (``tokens``, ``zipf_a``)."""
    return Tokens(cfg["vocab_size"], traffic["batch"], traffic["seq"], seed,
                  traffic.get("tokens", "zipf"), traffic.get("zipf_a", 1.3))
