"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state. The single-pod mesh
is 16x16 = 256 chips (``data`` x ``model``); the multi-pod mesh prepends a
``pod`` axis: 2 x 16 x 16 = 512 chips. Data parallelism spans
(pod, data); tensor/expert parallelism spans ``model`` (intra-pod, where
ICI is fastest); the ``pod`` axis only ever carries gradient all-reduces
and ZeRO state, which tolerate the slower inter-pod DCN links.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A (data, model) mesh over the first ``data * model`` local devices.

    Raises ``ValueError`` when fewer devices exist than the mesh asks
    for: a silently smaller mesh would run a different program than the
    one requested.
    """
    n = len(jax.devices())
    if data < 1 or model < 1 or data * model > n:
        raise ValueError(f"mesh data={data} x model={model} needs "
                         f"{data * model} devices; {n} available")
    return _make_mesh((data, model), ("data", "model"))
