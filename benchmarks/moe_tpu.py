"""The dropless expert layer on one TPU chip: grouped matmuls compared.

At Moonlight-16B-A3B's widths (d_model 2048, experts of 1408, top 6 of 64
experts, 8 of them held here, bf16) and ``--tokens`` tokens a step, with
a router of random weights (about T * 6 / 64 rows per held expert):

- the held experts' SwiGLU (gate/up and down products) on rows sorted by
  expert, forward and forward + backward, through ``jax.lax.ragged_dot``
  and through the megablox ``gmm`` kernel at each tiling of the sweep,
  and how far each one's output is from ``ragged_dot``'s;
- the whole layer (``moe.apply_moe``: router, permutation, experts,
  shared experts), forward + backward, with each grouped matmul.

    python benchmarks/moe_tpu.py [--tokens 16384] [--out chiprun_out/moe_tpu.json]

It needs a TPU and exits non-zero without one. Each timing is the median
of 10 calls ended by ``block_until_ready``, after a call that compiles.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

REPS = 10
TILINGS = [(128, 128, 128), (256, 512, 512), (512, 512, 512),
           (512, 1024, 1024), (1024, 512, 1024)]


def _time(fn, *args, reps: int = REPS):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return statistics.median(out) * 1e3


def _gmm(tiling):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def grouped(lhs, rhs, sizes):
        return gmm(lhs, rhs, sizes, lhs.dtype, tiling)
    return grouped


def _experts(grouped, f):
    def run(xs, wi, wo, sizes):
        gu = grouped(xs, wi, sizes)
        h = jax.nn.silu(gu[:, :f].astype(jnp.float32)).astype(xs.dtype) * gu[:, f:]
        return grouped(h, wo, sizes)
    return run


def _fwd_bwd(fn):
    """(forward, forward + backward in xs, wi, wo) of ``fn(xs, wi, wo, sizes)``."""
    @jax.jit
    def both(xs, wi, wo, sizes):
        out, vjp = jax.vjp(lambda *w: fn(*w, sizes), xs, wi, wo)
        return out, vjp(jnp.ones_like(out))
    return jax.jit(fn), both


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--out", default="chiprun_out/moe_tpu.json")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models import moe
    from repro.models.module import init_params

    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), experts_held=8)
    d, f, held, k = cfg.d_model, cfg.expert_d_ff, cfg.held_experts, cfg.top_k
    t = args.tokens
    model = build_model(cfg)
    spec = model.param_spec()["layers"]["L0"]["mlp"]
    p = jax.tree.map(lambda a: a[0],
                     init_params(spec, jax.random.key(0), jnp.bfloat16))
    x = jax.random.normal(jax.random.key(1), (1, t, d), jnp.bfloat16)

    # rows as the layer sorts them, and the group sizes random routing gives
    idx, _ = moe.route(p, cfg, x[0])
    local = idx.reshape(-1)
    group = jnp.where(local < held, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
    routed = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(routed, x[0][order // k], 0)
    wi = p["wi"].reshape(held, d, 2 * f)
    out = {"device": {"platform": dev.platform, "device_kind": dev.device_kind},
           "tokens": t, "rows_per_held_expert": [int(s) for s in sizes],
           "experts": [], "layer": []}
    print(json.dumps({"sizes": out["rows_per_held_expert"]}), flush=True)

    impls = {"ragged_dot": jax.lax.ragged_dot}
    impls.update({f"gmm{tl}": _gmm(tl) for tl in TILINGS})
    want = None
    for name, grouped in impls.items():
        try:
            run = _experts(grouped, f)
            fwd, both = _fwd_bwd(lambda xs, wi, wo, sizes: jnp.where(
                routed, run(xs, wi, wo, sizes), 0))
            y = jax.block_until_ready(fwd(xs, wi, p["wo"], sizes))
            if want is None:
                want = y.astype(jnp.float32)
            err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - want))
                        / jnp.max(jnp.abs(want)))
            r = {"impl": name, "fwd_ms": _time(fwd, xs, wi, p["wo"], sizes),
                 "fwd_bwd_ms": _time(both, xs, wi, p["wo"], sizes),
                 "err_vs_ragged_dot": err}
        except Exception as e:  # a tiling the kernel refuses
            r = {"impl": name, "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps(r), flush=True)
        out["experts"].append(r)

    ok = [r for r in out["experts"] if "fwd_bwd_ms" in r]
    best = min(ok, key=lambda r: r["fwd_bwd_ms"])["impl"]
    for name in dict.fromkeys(["ragged_dot", best]):
        was = moe._grouped
        moe._grouped = impls[name]
        try:
            loss = lambda p, x: jnp.sum(moe.apply_moe(p, cfg, x)[0].astype(jnp.float32))
            both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            r = {"impl": name, "layer_fwd_bwd_ms": _time(both, p, x)}
        finally:
            moe._grouped = was
        print(json.dumps(r), flush=True)
        out["layer"].append(r)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
