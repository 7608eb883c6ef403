"""The stacked sequence model covering all ten assigned architectures.

One class, parameterized by ``ModelConfig``: dense / MoE / hybrid(SSM+attn) /
VLM(cross-attn) / enc-dec(audio) / pure-SSM stacks are all instances of a
*periodic layer pattern* scanned with ``jax.lax.scan`` over stacked
parameters (HLO size O(period), compile time independent of depth).
Leading dense layers (``cfg.first_dense_layers``, DeepSeek's
``first_k_dense_replace``) run ahead of that scan in a scan of their own,
under the same remat; their weights sit under ``dense_layers``.

Entry points:
  ``loss(params, batch)``          training objective (+ metrics)
  ``forward(params, batch)``       full-sequence logits
  ``prefill(params, batch)``       logits + populated decode cache
  ``decode_step(params, cache, tokens, pos)``  one-token serving step
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.module import (count_params, init_params, logical_axes,
                                 shape_tree, spec, stack_specs)

Pytree = Any


def _dtype_of(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[cfg.dtype]


class StackModel:
    def __init__(self, cfg: ModelConfig, sharder: Optional[Callable] = None):
        self.cfg = cfg
        self.dtype = _dtype_of(cfg)
        self.pattern = cfg.pattern()
        self.lead = [("attn", "dense")] if cfg.first_dense_layers else []
        self.sharder = sharder  # name -> Sharding | None

    # ------------------------------------------------------------------
    # Parameter specs
    # ------------------------------------------------------------------
    def _layer_spec(self, mixer: str, mlp: str) -> Dict:
        cfg = self.cfg
        p: Dict[str, Any] = {"norm1": L.norm_spec(cfg)}
        if mixer == "attn" or mixer == "enc_attn":
            p["mixer"] = attn.attn_spec(cfg)
        elif mixer == "cross":
            p["mixer"] = attn.attn_spec(cfg)
            p["gate_attn"] = spec((), (), "zeros", dtype=jnp.float32)
        elif mixer == "attn_cross":
            p["mixer"] = attn.attn_spec(cfg)
            p["norm_x"] = L.norm_spec(cfg)
            p["cross"] = attn.attn_spec(cfg)
        elif mixer == "ssm":
            p["mixer"] = ssm_lib.ssm_spec(cfg)
        else:
            raise ValueError(mixer)
        if mlp != "none":
            p["norm2"] = L.norm_spec(cfg)
        if mlp == "dense":
            p["mlp"] = L.mlp_spec(cfg)
        elif mlp == "moe":
            p["mlp"] = moe_lib.moe_spec(cfg)
        elif mlp == "moe_dense":
            p["mlp"] = moe_lib.moe_spec(cfg)
            p["mlp_dense"] = L.mlp_spec(cfg)
        return p

    def param_spec(self) -> Pytree:
        cfg = self.cfg
        layer_specs = {}
        for i, (mixer, mlp) in enumerate(self.pattern):
            layer_specs[f"L{i}"] = self._layer_spec(mixer, mlp)
        tree = {
            "embed": L.embed_spec(cfg),
            "layers": stack_specs(layer_specs, cfg.num_periods, None),
            "final_norm": L.norm_spec(cfg),
        }
        if self.lead:
            tree["dense_layers"] = stack_specs(
                {"L0": self._layer_spec(*self.lead[0])},
                cfg.first_dense_layers, None)
        if cfg.is_encoder_decoder:
            enc = {f"L{i}": self._layer_spec("enc_attn", "dense")
                   for i in range(1)}  # encoder period is 1
            tree["encoder"] = stack_specs(enc, cfg.encoder_layers, None)
            tree["enc_norm"] = L.norm_spec(cfg)
        return tree

    def init(self, key) -> Pytree:
        return init_params(self.param_spec(), key, self.dtype)

    def init_shape(self) -> Pytree:
        return shape_tree(self.param_spec(), self.dtype)

    def param_axes(self) -> Pytree:
        return logical_axes(self.param_spec())

    def param_count(self, active_only: bool = False) -> int:
        spec_tree = self.param_spec()
        total = count_params(spec_tree)
        if not active_only or not self.cfg.num_experts:
            return total
        # Scale expert tensors by top_k / num_experts.
        cfg = self.cfg
        inactive = 0
        for path, leaf in jax.tree.flatten_with_path(
                spec_tree, is_leaf=lambda x: hasattr(x, "axes"))[0]:
            keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            if "mlp" in keys and "experts" in leaf.axes:
                n = math.prod(leaf.shape)
                inactive += int(n * (1 - cfg.top_k / cfg.num_experts))
        return total - inactive

    # ------------------------------------------------------------------
    # Forward pass
    # ------------------------------------------------------------------
    def _norm(self, p, x):
        return L.apply_norm(p, x, self.cfg.norm_eps)

    def _constrain(self, x, name):
        if self.sharder is None:
            return x
        s = self.sharder(name, x.shape)
        return jax.lax.with_sharding_constraint(x, s) if s is not None else x

    def _apply_layer(self, kind, p, x, positions, memory):
        cfg = self.cfg
        mixer, mlp = kind
        h = self._norm(p["norm1"], x)
        if mixer == "attn":
            y, _ = attn.apply_self_attn(p["mixer"], cfg, h, positions,
                                        shard=self.sharder)
        elif mixer == "enc_attn":
            y, _ = attn.apply_self_attn(p["mixer"], cfg, h, positions,
                                        shard=self.sharder, causal=False)
        elif mixer == "cross":
            y, _ = attn.apply_cross_attn(p["mixer"], cfg, h, memory)
            y = y * jnp.tanh(p["gate_attn"]).astype(y.dtype)
        elif mixer == "attn_cross":
            y, _ = attn.apply_self_attn(p["mixer"], cfg, h, positions)
            x = x + y
            hx = self._norm(p["norm_x"], x)
            y, _ = attn.apply_cross_attn(p["cross"], cfg, hx, memory)
        elif mixer == "ssm":
            y, _ = ssm_lib.apply_ssm(p["mixer"], cfg, h)
        else:
            raise ValueError(mixer)
        x = x + y
        x = self._constrain(x, "acts")
        x, aux = self._mlp_block(mlp, p, x)
        if mlp != "none":
            x = self._constrain(x, "acts")
        return x, aux

    def _remat_wrap(self, f):
        cfg = self.cfg
        if cfg.remat == "none":
            return f
        if cfg.remat == "dots":
            pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            return jax.checkpoint(f, policy=pol)
        return jax.checkpoint(f)

    def _run_stack(self, params, x, positions, memory):
        def scan(kinds, carry, stacked):
            def body(carry, layer_params):
                h, aux = carry
                for i, kind in enumerate(kinds):
                    h, a = self._apply_layer(kind, layer_params[f"L{i}"], h,
                                             positions, memory)
                    aux = aux + a
                return (h, aux), None

            return jax.lax.scan(self._remat_wrap(body), carry, stacked)[0]

        carry = (x, jnp.zeros((), jnp.float32))
        if self.lead:
            carry = scan(self.lead, carry, params["dense_layers"])
        return scan(self.pattern, carry, params["layers"])

    def _encode(self, params, frames):
        """Whisper-style encoder over stub frame embeddings (B,M,D)."""
        x = frames.astype(self.dtype)

        def body(h, layer_params):
            h, _ = self._apply_layer_generic(layer_params["L0"], h,
                                             "enc_attn", "dense")
            return h, None

        body = self._remat_wrap(body)
        x, _ = jax.lax.scan(body, x, params["encoder"])
        return self._norm(params["enc_norm"], x)

    def _apply_layer_generic(self, p, x, mixer, mlp):
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        h = self._norm(p["norm1"], x)
        y, _ = attn.apply_self_attn(p["mixer"], self.cfg, h, positions,
                                    shard=self.sharder, causal=False)
        x = x + y
        h = self._norm(p["norm2"], x)
        x = x + L.apply_mlp(p["mlp"], h)
        return x, None

    def _memory_of(self, params, batch):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            return self._encode(params, batch["frames"])
        if cfg.cross_every:
            return batch["patches"].astype(self.dtype)
        return None

    def forward(self, params, batch) -> jax.Array:
        """batch: {"tokens": (B,S) int32, ...modality inputs}. -> logits."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = L.embed_tokens(params["embed"], tokens, self.dtype)
        x = self._constrain(x, "acts")
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        memory = self._memory_of(params, batch)
        x, aux = self._run_stack(params, x, positions, memory)
        x = self._norm(params["final_norm"], x)
        logits = L.unembed(params["embed"], x, cfg.logits_softcap, cfg.vocab_size)
        return logits, aux

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        ce = L.cross_entropy(logits, batch["labels"], batch.get("mask"))
        total = ce + aux
        return total, {"loss": total, "ce": ce, "aux": aux}

    # ------------------------------------------------------------------
    # Serving: prefill + decode
    # ------------------------------------------------------------------
    def _layer_cache_spec(self, mixer: str, batch: int, seq: int):
        cfg = self.cfg
        kvs = attn.kv_cache_shape(cfg, batch, seq)
        ca = ("batch", "cache_seq", "kv_heads", "head_dim")
        if mixer in ("attn",):
            return {k: (v, ca, self._cache_dtype) for k, v in kvs.items()}
        if mixer == "cross":
            m = cfg.vision_seq
            kvs = attn.kv_cache_shape(cfg, batch, m)
            return {k: (v, ca, self._cache_dtype) for k, v in kvs.items()}
        if mixer == "attn_cross":
            out = {k: (v, ca, self._cache_dtype) for k, v in kvs.items()}
            kvm = attn.kv_cache_shape(cfg, batch, cfg.audio_seq)
            out.update({f"x{k}": (v, ca, self._cache_dtype)
                        for k, v in kvm.items()})
            return out
        if mixer == "ssm":
            shp = ssm_lib.ssm_cache_shape(cfg, batch)
            axes = {"state": ("batch", "heads", None, None),
                    "conv_x": ("batch", None, "mlp"),
                    "conv_b": ("batch", None, None),
                    "conv_c": ("batch", None, None)}
            return {k: (v, axes[k], jnp.float32 if k == "state" else self._cache_dtype)
                    for k, v in shp.items()}
        raise ValueError(mixer)

    @property
    def _cache_dtype(self):
        return self.dtype

    def cache_spec(self, batch: int, seq: int):
        """Returns (shape_tree, logical_axes_tree) for the decode cache: per
        layer of the period ``L<i>``, stacked over the periods, and the
        leading dense layers' under ``dense_layers``."""
        def group(kinds, n):
            shapes, axes = {}, {}
            for i, (mixer, _) in enumerate(kinds):
                entry = self._layer_cache_spec(mixer, batch, seq)
                shapes[f"L{i}"] = {k: jax.ShapeDtypeStruct((n,) + shp, dt)
                                   for k, (shp, ax, dt) in entry.items()}
                axes[f"L{i}"] = {k: (None,) + ax for k, (shp, ax, dt) in entry.items()}
            return shapes, axes

        shapes, axes = group(self.pattern, self.cfg.num_periods)
        if self.lead:
            shapes["dense_layers"], axes["dense_layers"] = group(
                self.lead, self.cfg.first_dense_layers)
        return shapes, axes

    def init_cache(self, batch: int, seq: int):
        shapes, _ = self.cache_spec(batch, seq)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def _mlp_block(self, mlp: str, p, h):
        """The residual MLP branch of one layer -> (h, the MoE aux loss)."""
        aux = jnp.zeros((), jnp.float32)
        if mlp == "none":
            return h, aux
        hn = self._norm(p["norm2"], h)
        if mlp == "dense":
            y = L.apply_mlp(p["mlp"], hn)
        else:
            y, aux = moe_lib.apply_moe(p["mlp"], self.cfg, hn, shard=self.sharder)
            if mlp == "moe_dense":
                y = y + L.apply_mlp(p["mlp_dense"], hn)
        return h + y, aux

    def _prefill_layer(self, kind, p, h, positions, memory):
        cfg = self.cfg
        mixer, mlp = kind
        hn = self._norm(p["norm1"], h)
        cache_i = {}
        if mixer == "attn":
            y, (k, v) = attn.apply_self_attn(p["mixer"], cfg, hn, positions)
            cache_i = {"k": k.astype(self._cache_dtype),
                       "v": v.astype(self._cache_dtype)}
        elif mixer == "cross":
            y, (k, v) = attn.apply_cross_attn(p["mixer"], cfg, hn, memory)
            y = y * jnp.tanh(p["gate_attn"]).astype(y.dtype)
            cache_i = {"k": k.astype(self._cache_dtype),
                       "v": v.astype(self._cache_dtype)}
        elif mixer == "attn_cross":
            y, (k, v) = attn.apply_self_attn(p["mixer"], cfg, hn, positions)
            h = h + y
            hx = self._norm(p["norm_x"], h)
            y, (xk, xv) = attn.apply_cross_attn(p["cross"], cfg, hx, memory)
            cache_i = {"k": k.astype(self._cache_dtype),
                       "v": v.astype(self._cache_dtype),
                       "xk": xk.astype(self._cache_dtype),
                       "xv": xv.astype(self._cache_dtype)}
        elif mixer == "ssm":
            y, cache_i = ssm_lib.apply_ssm(p["mixer"], cfg, hn, return_cache=True)
        h, _ = self._mlp_block(mlp, p, h + y)
        return self._constrain(h, "acts"), cache_i

    def _decode_layer(self, kind, p, c, h, pos):
        cfg = self.cfg
        mixer, mlp = kind
        hn = self._norm(p["norm1"], h)
        if mixer == "attn":
            y, c = attn.decode_self_attn(p["mixer"], cfg, hn, c, pos,
                                         shard=self.sharder)
        elif mixer == "cross":
            y, c = attn.decode_cross_attn(p["mixer"], cfg, hn, c)
            y = y * jnp.tanh(p["gate_attn"]).astype(y.dtype)
        elif mixer == "attn_cross":
            y, sc = attn.decode_self_attn(
                p["mixer"], cfg, hn, {"k": c["k"], "v": c["v"]}, pos,
                shard=self.sharder)
            h = h + y
            hx = self._norm(p["norm_x"], h)
            y, _ = attn.decode_cross_attn(
                p["cross"], cfg, hx, {"k": c["xk"], "v": c["xv"]})
            c = {**sc, "xk": c["xk"], "xv": c["xv"]}
        elif mixer == "ssm":
            y, c = ssm_lib.decode_ssm(p["mixer"], cfg, hn, c)
        return self._mlp_block(mlp, p, h + y)[0], c

    def prefill(self, params, batch):
        """Full-sequence forward that also builds the decode cache.

        Returns (last_token_logits, cache).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = L.embed_tokens(params["embed"], tokens, self.dtype)
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        memory = self._memory_of(params, batch)

        def scan(kinds, h, stacked):
            def body(h, layer_params):
                caches = {}
                for i, kind in enumerate(kinds):
                    h, caches[f"L{i}"] = self._prefill_layer(
                        kind, layer_params[f"L{i}"], h, positions, memory)
                return h, caches

            return jax.lax.scan(self._remat_wrap(body), h, stacked)

        if self.lead:
            x, lead_cache = scan(self.lead, x, params["dense_layers"])
        x, cache = scan(self.pattern, x, params["layers"])
        if self.lead:
            cache["dense_layers"] = lead_cache
        x = self._norm(params["final_norm"], x)
        logits = L.unembed(params["embed"], x[:, -1:], cfg.logits_softcap, cfg.vocab_size)
        return logits, cache

    def decode_step(self, params, cache, tokens, pos, memory=None):
        """tokens (B,1) int32; pos (B,) write positions. -> (logits, cache)."""
        cfg = self.cfg
        x = L.embed_tokens(params["embed"], tokens, self.dtype)

        def scan(kinds, h, stacked, caches):
            def body(h, xs):
                layer_params, layer_cache = xs
                new_cache = {}
                for i, kind in enumerate(kinds):
                    h, new_cache[f"L{i}"] = self._decode_layer(
                        kind, layer_params[f"L{i}"], layer_cache[f"L{i}"], h, pos)
                return h, new_cache

            return jax.lax.scan(body, h, (stacked, caches))

        cache = dict(cache)
        lead_cache = cache.pop("dense_layers", None)
        if self.lead:
            x, lead_cache = scan(self.lead, x, params["dense_layers"], lead_cache)
        x, new_cache = scan(self.pattern, x, params["layers"], cache)
        if self.lead:
            new_cache["dense_layers"] = lead_cache
        x = self._norm(params["final_norm"], x)
        logits = L.unembed(params["embed"], x, cfg.logits_softcap, cfg.vocab_size)
        return logits, new_cache
