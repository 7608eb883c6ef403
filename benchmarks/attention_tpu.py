"""Causal attention on one TPU chip: the flash kernel against XLA.

At an architecture's attention widths (``--arch``; by default qwen2-0.5b:
14 query heads over 2 KV heads of 64; Moonlight-16B-A3B's latent
attention: 16 heads, q/k 192 and v 128 wide), bf16, and ``--tokens``
tokens a step, for each sequence length:

- the kernel's outputs and gradients against an f32 reference (at most
  4096 tokens, where the reference's S x S tensors fit);
- one layer's RoPE and attention core (``causal_attention``), forward and
  forward + backward, on the XLA path, on the kernel at each (block_q,
  block_k) of the sweep, and through the bundled splash-attention kernel
  as a yardstick where q, k and v are of one width;
- for qwen2-0.5b, the launcher's whole donated train step with attention
  pinned to XLA
  and to the kernel (at the sweep's best blocks, or with ``--no-layer`` at
  ``ops.flash_blocks``'s); with ``--profile``, the
  device time per step of its named scopes (``flash`` apart from the rest
  of ``attention``) and of its longest ops, from a profiler trace.

    python benchmarks/attention_tpu.py [--arch qwen2-0.5b] [--tokens 8192]
        [--seqs 512 1024 2048 4096] [--blocks 256 512 1024 2048]
        [--no-layer] [--profile]
        [--out chiprun_out/attention_tpu.json]

It needs a TPU and exits non-zero without one. A layer's timing is the
median of 10 calls, a step's of 5, each ended by ``block_until_ready``,
after warm-up calls that compile.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp

TOKENS = 8192
REPS, STEP_REPS = 10, 5


def _time(fn, *args, reps: int):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return statistics.median(out) * 1e3


def _fwd_bwd(attn):
    """(forward, forward + backward) of ``attn(q, k, v)`` as jitted calls."""
    fwd = jax.jit(attn)

    @jax.jit
    def both(q, k, v, do):
        o, vjp = jax.vjp(attn, q, k, v)
        return o, vjp(do)

    return fwd, both


def _widths(cfg):
    """(q/k head width, v head width) of an architecture's self-attention."""
    if cfg.kv_lora_rank:
        return cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    return cfg.resolved_head_dim, cfg.resolved_head_dim


def _inputs(batch, seq, cfg, dtype=jnp.bfloat16):
    h, kv = cfg.num_heads, cfg.num_kv_heads
    dqk, dv = _widths(cfg)
    ks = jax.random.split(jax.random.key(seq), 4)
    q = jax.random.normal(ks[0], (batch, seq, h, dqk), dtype)
    k = jax.random.normal(ks[1], (batch, seq, kv, dqk), dtype)
    v = jax.random.normal(ks[2], (batch, seq, kv, dv), dtype)
    do = jax.random.normal(ks[3], (batch, seq, h, dv), dtype)
    return q, k, v, do


def check(batch, seq, cfg, blocks):
    """Largest |kernel - f32 reference| over largest |reference|, per output."""
    from repro.kernels import ops
    from repro.models import attention as A

    q, k, v, do = _inputs(batch, seq, cfg)
    _, both = _fwd_bwd(lambda q, k, v: ops.flash_attention(q, k, v, blocks=blocks))
    f32 = lambda t: t.astype(jnp.float32)

    def ref(q, k, v):
        mask = (jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None])[None, None]
        return A.dot_attention(q, A._expand_kv(cfg, k), A._expand_kv(cfg, v), mask)

    with jax.default_matmul_precision("highest"):
        _, ref_both = _fwd_bwd(ref)
        want = ref_both(f32(q), f32(k), f32(v), f32(do))
    got = both(q, k, v, do)
    names = ("o", "dq", "dk", "dv")
    flat = lambda t: [t[0], *t[1]]
    return {n: float(jnp.max(jnp.abs(f32(g) - w)) / jnp.max(jnp.abs(w)))
            for n, g, w in zip(names, flat(got), flat(want))}


def layer_times(batch, seq, cfg, blocks_sweep, reps):
    """One layer's RoPE and attention core, as ``causal_attention`` runs
    them, on each path."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    from repro.kernels import ops
    from repro.models import attention as A

    q, k, v, do = _inputs(batch, seq, cfg)
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :]
    rows = []

    def row(name, impl, attn, **extra):
        with A.attention_impl(impl):
            fwd, both = _fwd_bwd(attn)
            f = _time(fwd, q, k, v, reps=reps)
            fb = _time(both, q, k, v, do, reps=reps)
        r = {"seq": seq, "batch": batch, "path": name, "fwd_ms": f,
             "bwd_ms": fb - f, "fwd_bwd_ms": fb, **extra}
        print(json.dumps(r), flush=True)
        rows.append(r)

    model_path = lambda q, k, v: A.causal_attention(cfg, q, k, v, pos)[0]
    row("xla", "xla", model_path)
    table = dict(ops.FLASH_BLOCKS)
    for bq, bk in blocks_sweep:
        if seq % bq or seq % bk:
            continue
        ops.FLASH_BLOCKS[seq, _widths(cfg)[0]] = (bq, bk)
        row("flash", "pallas", lambda q, k, v: model_path(q, k, v),
            block_q=bq, block_k=bk)
    ops.FLASH_BLOCKS.clear()
    ops.FLASH_BLOCKS.update(table)
    # the yardstick: splash attention per batch row, after the same RoPE,
    # q pre-scaled as the kernel's
    h = cfg.num_heads
    dqk, dv = _widths(cfg)
    scale = dqk ** -0.5
    heads = lambda t: jnp.swapaxes(t, 1, 2)
    for b in (512, 1024):
        if seq % b or dqk != dv:
            continue
        sizes = splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                                  block_q_dkv=b, block_kv_dkv=b,
                                  block_kv_dkv_compute=b, block_q_dq=b,
                                  block_kv_dq=b)
        mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * h)
        kern = splash.make_splash_mha_single_device(mask, block_sizes=sizes)

        def attn(q, k, v, kern=kern):
            q = A._apply_rope(cfg, q, pos) * jnp.asarray(scale, q.dtype)
            k = A._apply_rope(cfg, k, pos)
            return heads(jax.vmap(kern)(heads(q), heads(k), heads(v)))

        row("splash", "xla", attn, block_q=b, block_k=b)
    return rows


SCOPES = ("flash", "attention", "mlp", "norm", "lm_head", "embed", "optimizer")


def _scope(op_name: str) -> str:
    for tok in reversed(re.findall(r"[^/()]+", op_name)):
        if tok in SCOPES:
            return tok
    return "other"


def profile(compiled, state, batch_, steps=3, top=6):
    """Device ms per step by scope and of each scope's ``top`` longest ops,
    from a profiler trace of ``steps`` steps. The layer scan's ``while``
    ops hold the others and are left out."""
    import tempfile

    from chipbench import xtrace

    names = xtrace.op_names(compiled.as_text())
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(steps):
            state, metrics = compiled(state, batch_)
            jax.block_until_ready((state, metrics))
        jax.profiler.stop_trace()
        data = jax.profiler.ProfileData.from_file(xtrace.find_xplane(d))
        scopes, ops = {}, {}
        for plane in data.planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name != xtrace.OPS_LINE:
                    continue
                for e in line.events:
                    op = xtrace.op_name(e.name)
                    if op.startswith("while"):
                        continue
                    sc = _scope(names.get(op.split(" ")[0], ""))
                    ms = e.duration_ns * 1e-6 / steps
                    scopes[sc] = scopes.get(sc, 0.0) + ms
                    ops[op, sc] = ops.get((op, sc), 0.0) + ms
    longest = {sc: sorted(((ms, op) for (op, s), ms in ops.items() if s == sc),
                          reverse=True)[:top] for sc in scopes}
    return state, {"scope_ms": scopes, "top_ops_ms": longest}


def step_time(batch, seq, impl, reps, opt, traced=False):
    """The launcher's donated train step at full width, attention pinned."""
    from repro.configs import get_config
    from repro.models import attention as A
    from repro.models import build_model
    from repro.train import optimizer as opt_lib
    from repro.train import step as step_lib
    from repro.train.loop import LoopConfig, Trainer

    with A.attention_impl(impl):
        model = build_model(get_config("qwen2-0.5b"))
        opt_cfg = opt_lib.OptConfig(**opt)
        trainer = Trainer(model, opt_cfg, LoopConfig(batch=batch, seq=seq))
        state = jax.jit(lambda key: step_lib.init_state(model, opt_cfg, key))(
            jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                  model.cfg.vocab_size, jnp.int32)
        batch_ = {"tokens": toks, "labels": toks}
        t0 = time.perf_counter()
        compiled = trainer.jstep.lower(state, batch_).compile()
        compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    mem = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    times = []
    for i in range(reps + 2):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch_)
        jax.block_until_ready((state, metrics))
        if i >= 2:
            times.append(time.perf_counter() - t0)
    loss = float(metrics["loss"])
    extra = {}
    if traced:
        state, extra = profile(compiled, state, batch_)
    del state
    r = {**extra, "seq": seq, "batch": batch, "path": impl, "step_ms": statistics.median(times) * 1e3,
         "tokens_per_s": batch * seq / statistics.median(times), "compile_s": compile_s,
         "footprint_gib": mem / 2**30, "loss_after": loss,
         "custom_calls": compiled.as_text().count("tpu_custom_call")}
    print(json.dumps(r), flush=True)
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--tokens", type=int, default=TOKENS)
    ap.add_argument("--seqs", type=int, nargs="+", default=[512, 1024, 2048, 4096])
    ap.add_argument("--blocks", type=int, nargs="+", default=[256, 512, 1024, 2048])
    ap.add_argument("--no-layer", action="store_true")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--out", default="chiprun_out/attention_tpu.json")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.kernels import ops

    cfg = get_config(args.arch)
    dqk = _widths(cfg)[0]
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "chipbench", "traffic", "train_b8s1024.json")) as f:
        opt = json.load(f)["optimizer"]
    out = {"device": {"platform": dev.platform, "device_kind": dev.device_kind,
                      "count": jax.device_count()}, "arch": args.arch,
           "check": {}, "layer": [], "step": []}
    sweep = [(bq, bk) for bq, bk in itertools.product(args.blocks, args.blocks)
             if bq <= bk]
    best = {}
    for seq in [] if args.no_layer else args.seqs:
        batch = args.tokens // seq
        # batch 1 and at most 4096 tokens: the f32 reference holds its
        # S x S tensors
        cs = min(seq, 4096)
        out["check"][seq] = check(1, cs, cfg, ops.flash_blocks(cs, dqk))
        print(json.dumps({"seq": seq, "check": out["check"][seq]}), flush=True)
        rows = layer_times(batch, seq, cfg, sweep, REPS)
        out["layer"] += rows
        flash = [r for r in rows if r["path"] == "flash"]
        b = min(flash, key=lambda r: r["fwd_bwd_ms"])
        best[seq] = (b["block_q"], b["block_k"])
    out["best_blocks"] = {s: list(b) for s, b in best.items()}
    print(json.dumps({"best_blocks": out["best_blocks"]}), flush=True)
    for seq in args.seqs if args.arch == "qwen2-0.5b" else []:
        ops.FLASH_BLOCKS[seq, 64] = best.get(seq, ops.flash_blocks(seq, 64))
        for impl in ("xla", "pallas"):
            out["step"].append(step_time(TOKENS // seq, seq, impl, STEP_REPS,
                                         opt, args.profile))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
