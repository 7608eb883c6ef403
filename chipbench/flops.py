"""Model FLOPs per trained token, from a configuration file's sizes.

Model FLOPs count what the architecture needs, not what the program
executes: recomputed activations (remat), masked-out attention scores and
padded vocabulary columns are left out.

Per trained token (forward and backward, so three times the forward):
``6 * N``, where N counts every parameter once (``param_count``), plus
three times the sequence mixer's forward FLOPs beyond ``2 * N``
(``mixer_flops_per_token``: causal attention's products, an SSD scan).
Both come from the configuration's reference module, which states how
its architecture counts.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench import registry


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters of a configuration file, each counted once."""
    return registry.reference(cfg).param_count(cfg)


def mixer_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs of the sequence mixer per token (beyond 2 * N)."""
    return registry.reference(cfg).mixer_flops_per_token(cfg, seq)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    return 6.0 * param_count(cfg) + 3.0 * mixer_flops_per_token(cfg, seq)
