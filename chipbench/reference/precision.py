"""The matrix products of the plain reference, in two precisions.

``highest``: fp32 operands, fp32 products, ``Precision.HIGHEST`` (on a TPU
a float32 product otherwise runs in bf16 passes).

``int8``: the control, one step below the bf16 the configurations state,
and the step a TPU v5e tempts with (its int8 peak is twice its bf16
peak). Each operand of a product, and the gradient flowing back into it,
is scaled per tensor so that its largest magnitude maps to 127 and
rounded to an integer; products accumulate in fp32. Everything that is
not a product stays in fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def highest(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _int8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def int8(spec: str, a, b):
    def fwd(a, b):
        qa, qb = _int8(a), _int8(b)
        return highest(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda x, y: highest(spec, x, y), *res)
        return vjp(_int8(g))

    prod = jax.custom_vjp(lambda a, b: fwd(a, b)[0])
    prod.defvjp(fwd, bwd)
    return prod(a, b)


DOTS = {"highest": highest, "int8": int8}
