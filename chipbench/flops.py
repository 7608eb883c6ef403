"""Model FLOPs per trained token, from a configuration file's sizes.

Model FLOPs count what the architecture needs, not what the program
executes: recomputed activations (remat), masked-out attention scores and
padded vocabulary columns are left out.

Per trained token (forward and backward, so three times the forward):

- ``6 * N``, where N counts every parameter once. The tied embedding table
  is counted once, as the LM head's matrix; its lookup is a gather and
  costs nothing. Norm scales and biases are counted like weights.
- causal attention: the forward's Q.K and P.V products
  take ``2 * 2 * heads * head_dim`` FLOPs per (query, key) pair, and a
  query at position i sees i + 1 keys, (S + 1) / 2 on average. So
  ``3 * 4 * layers * heads * head_dim * (S + 1) / 2`` per token.
"""

from __future__ import annotations

from typing import Any, Dict


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters of a configuration file, each counted once."""
    if cfg["reference"] == "qwen2":
        d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
        h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = d // h
        attn = d * h * hd + 2 * d * kv * hd + h * hd * d + (h + 2 * kv) * hd
        mlp = 3 * d * f
        layer = attn + mlp + 2 * d
        head = v * d * (1 if cfg["tie_word_embeddings"] else 2)
        return cfg["num_hidden_layers"] * layer + head + d
    raise KeyError(f"no FLOP count for reference {cfg['reference']!r}")


def mixer_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs of the sequence mixer per token (beyond 2 * N)."""
    if cfg["reference"] == "qwen2":
        h = cfg["num_attention_heads"]
        hd = cfg["hidden_size"] // h
        return 4.0 * cfg["num_hidden_layers"] * h * hd * (seq + 1) / 2
    raise KeyError(f"no FLOP count for reference {cfg['reference']!r}")


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    return 6.0 * param_count(cfg) + 3.0 * mixer_flops_per_token(cfg, seq)
