"""Smoke run of the cost oracle's main path on TPU, at published widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded launcher

With one chip the phases run in this process, in this order, at the
published widths of qwen2-0.5b (and of mamba2-370m for the SSD kernel):

  1. device   -- JAX's first device must be a TPU; nothing falls back;
  2. kernels  -- the Pallas kernels, compiled for the chip, against the
                 pure-jnp references at full float32 matmul precision;
  3. profile  -- ``profile_lm`` on qwen2-0.5b at a few (batch, seq) points
                 and ``profile_zoo`` on the fast CNNs, all measured here;
  4. serve    -- fit ``DNNAbacus`` on those records, save it, and answer
                 queries through ``PredictionService`` + ``AbacusServer``:
                 the profiled shapes from a trace store, one unseen shape
                 by a cold trace;
  5. train    -- ``repro.launch.train`` at the unseen shape, admitted by
                 that predictor against the chip's own memory limit.

``--four-chips`` runs only the launcher on a 2 x 2 (data x model) mesh
and the same steps on one device, and compares their losses.

Any failure exits non-zero. Outputs go to ``chiprun_out/chip_smoke/``; the
run reads no file it did not write. The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.collect import FAST_NETS  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

OUT = ROOT / "chiprun_out" / "chip_smoke"
ARCH = "qwen2-0.5b"
# profiled shapes: at most 4096 tokens, so every profile-step memory label
# stays under the chip's limit (at 8192 tokens the label, which counts the
# undonated outputs beside the inputs, reads more than the chip holds)
PROFILED = ((2, 1024), (4, 1024), (8, 512), (2, 2048))
UNSEEN = (8, 1024)  # queried cold, then trained
TRAIN_STEPS = 3


def phase(name, fn, *args, **kwargs):
    print(f"[smoke] {name}: start", flush=True)
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    gc.collect()  # nothing of one phase stays on the device for the next
    print(f"[smoke] {name}: ok ({time.perf_counter() - t0:.1f} s wall)",
          flush=True)
    return out


def check_device(count: Optional[int]) -> dict:
    """JAX's devices, which must be TPUs: ``count`` of them, if given."""
    devices = jax.devices()
    d = devices[0]
    print(f"[smoke] device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"[smoke] no TPU: JAX's first device is "
                         f"{d.platform}")
    if count is not None and len(devices) != count:
        raise SystemExit(f"[smoke] {count} chips needed, "
                         f"{len(devices)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _close(name: str, got, want, bound: float, why: str) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape}, "
                             f"finite={bool(np.isfinite(got).all())}")
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(f"[smoke] {name}: max|out-ref|/max|ref| = {err!r} "
          f"(bound {bound}: {why})", flush=True)
    if not err <= bound:
        raise AssertionError(f"{name}: error {err} over bound {bound}")


def check_kernels(attn_cfg, ssd_cfg, mla_cfg, batch: int, seq: int,
                  interpret: bool = False) -> None:
    """Each Pallas kernel against its reference, on one set of inputs; the
    flash kernel also at ``mla_cfg``'s latent-attention widths (q/k heads
    wider than v heads)."""
    from repro.kernels import ops, ref
    from repro.models.ssm import ssd_chunked_ref

    keys = iter(jax.random.split(jax.random.key(0), 16))

    def rand(shape, dtype=jnp.float32, scale=1.0):
        x = scale * jax.random.normal(next(keys), shape, jnp.float32)
        return x.astype(dtype)

    f32 = lambda t: t.astype(jnp.float32)
    bf16_out = ("bf16 output: rounding is 2^-9 relative, and f32 operands "
                "may enter the MXU as bf16; 1e-2 is a few times both")

    def flash(name, h, kv, dqk, dv):
        q = rand((batch, seq, h, dqk), jnp.bfloat16)
        k = rand((batch, seq, kv, dqk), jnp.bfloat16)
        v = rand((batch, seq, kv, dv), jnp.bfloat16)
        got = ops.flash_attention(q, k, v, causal=True, interpret=interpret)
        # the reference takes every query head's own copy of its group's K/V
        flat = lambda t: f32(jnp.moveaxis(jnp.repeat(t, h // t.shape[2], axis=2),
                                          2, 1).reshape(batch * h, seq, t.shape[-1]))
        with jax.default_matmul_precision("highest"):
            want = ref.attention_ref(flat(q), flat(k), flat(v), causal=True)
        want = jnp.moveaxis(want.reshape(batch, h, seq, dv), 1, 2)
        _close(name, got, want, 1e-2, bf16_out)

    hd = attn_cfg.resolved_head_dim
    flash("flash_attention", attn_cfg.num_heads, attn_cfg.num_kv_heads, hd, hd)
    flash("flash_attention.mla", mla_cfg.num_heads, mla_cfg.num_kv_heads,
          mla_cfg.qk_nope_head_dim + mla_cfg.qk_rope_head_dim, mla_cfg.v_head_dim)

    d = attn_cfg.d_model
    x = rand((batch * seq, d), jnp.bfloat16)
    g = rand((d,))
    got = ops.rmsnorm(x, g, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = ref.rmsnorm_ref(f32(x), g)
    _close("rmsnorm", got, want, 1e-2, bf16_out)

    sh, sp, sn = ssd_cfg.ssm_heads, ssd_cfg.ssm_head_dim, ssd_cfg.ssm_state
    b = max(1, batch // 4)
    xb = rand((b, seq, sh, sp), jnp.bfloat16, 0.5)
    dt = jax.nn.softplus(rand((b, seq, sh)))
    a_neg = -jnp.exp(rand((sh,), scale=0.3))
    bm = rand((b, seq, sn), jnp.bfloat16, 0.5)
    cm = rand((b, seq, sn), jnp.bfloat16, 0.5)
    y, state = ops.ssd_scan(xb, dt, a_neg, bm, cm, ssd_cfg.ssm_chunk,
                            interpret=interpret)
    with jax.default_matmul_precision("highest"):
        y_ref, state_ref = ssd_chunked_ref(f32(xb), dt, a_neg, f32(bm),
                                           f32(cm), ssd_cfg.ssm_chunk)
    why = ("bf16 output and bf16 MXU passes over 256-term chunk sums "
           "and the carried state; 2e-2 is a few times their 2^-9 each")
    _close("ssd_scan.y", got=y, want=y_ref, bound=2e-2, why=why)
    _close("ssd_scan.state", got=state, want=state_ref, bound=2e-2, why=why)


def profile(cfg, shapes, zoo_nets) -> list:
    """Records measured in this run: the LM at ``shapes``, then the CNNs."""
    from repro.core.profiler import profile_lm, profile_zoo

    dev = jax.devices()[0]
    runs = ([lambda b=b, s=s: profile_lm(cfg, batch=b, seq=s, steps=3)
             for b, s in shapes]
            + [lambda n=n: profile_zoo(n, batch=16, steps=3)
               for n in zoo_nets])
    records = []
    for run in runs:
        r = run()
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"[smoke] profiled {r.model_name} b{r.batch_size} "
              f"in{r.input_size}: time_s={r.time_s!r} "
              f"mem_bytes label={r.mem_bytes!r} "
              f"process peak_bytes_in_use={peak!r}", flush=True)
        if not (r.time_s > 0 and r.mem_bytes > 0):
            raise AssertionError(f"empty profile {r}")
        records.append(r)
    return records


def fit_and_serve(records, cfg, shapes, unseen, out: pathlib.Path) -> str:
    """Fit, save, and answer (cfg, batch, seq) queries through the server.

    The profiled shapes are answered from a trace store holding their
    measured records; ``unseen`` needs a cold trace."""
    from repro.core.predictor import DNNAbacus
    from repro.serve.prediction_service import PredictionService
    from repro.serve.server import AbacusServer
    from repro.serve.trace_store import make_trace_store

    abacus = DNNAbacus().fit(records)
    path = str(out / "abacus")
    abacus.save(path)
    store = make_trace_store(str(out / "trace_store"))
    service = PredictionService(DNNAbacus.load(path), store=store)
    lm = [r for r in records if r.model_name == cfg.name]
    for r in lm:
        store.put(service.cache_key(cfg, r.batch_size, r.input_size), r)
    queries = list(shapes) + [unseen]
    with AbacusServer(service) as server:
        futures = [server.submit(cfg, b, s) for b, s in queries]
        estimates = [f.result(timeout=900) for f in futures]
    for (b, s), est in zip(queries, estimates):
        measured = next((r for r in lm if (r.batch_size, r.input_size)
                         == (b, s)), None)
        print(f"[smoke] query {cfg.name} b{b} s{s}: "
              f"time_s={est['time_s']!r} memory_bytes="
              f"{est['memory_bytes']!r}" + (
                  f" (measured {measured.time_s!r}, {measured.mem_bytes!r})"
                  if measured else " (unseen)"), flush=True)
        if not all(math.isfinite(est[k]) and est[k] > 0
                   for k in ("time_s", "memory_bytes")):
            raise AssertionError(f"bad estimate {est}")
    info = service.cache_info()
    print(f"[smoke] service: {json.dumps(info, sort_keys=True)}", flush=True)
    if (info["store_hits"], info["traces"]) != (len(shapes), 1):
        raise AssertionError(f"expected {len(shapes)} store hits and one "
                             f"cold trace, got {info}")
    return path


def launch(argv, out: pathlib.Path, name: str) -> list:
    """``repro.launch.train.main(argv)``; returns its per-step log."""
    from repro.launch import train

    log_path = out / f"{name}.jsonl"
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = train.main(list(argv) + ["--log", str(log_path)])
    print(buf.getvalue(), end="", flush=True)
    if rc != 0:
        raise AssertionError(f"launcher {name} exited {rc}")
    if "--predict" in argv and "[abacus] predicted" not in buf.getvalue():
        raise AssertionError("no admission line from the launcher")
    with open(log_path) as f:
        log = [json.loads(line) for line in f]
    if not log or not all(math.isfinite(r["loss"]) for r in log):
        raise AssertionError(f"launcher {name}: losses {log}")
    return log


def train(predictor: str, batch: int, seq: int, out: pathlib.Path,
          full_size: bool = True) -> None:
    argv = ["--arch", ARCH, "--steps", str(TRAIN_STEPS), "--batch",
            str(batch), "--seq", str(seq), "--predict",
            "--predictor-path", predictor]
    launch(argv + (["--full-size"] if full_size else []), out, "train")


# step 0 runs the same weights on the same batch; only the reduction order
# differs between the meshes, and the bf16 activations round to 2^-8, so
# the two mean losses agree far inside 1e-2 relative. Later steps apply
# updates computed in those two orders, so they may drift a little more.
STEP0_RTOL = 1e-2
LATER_RTOL = 5e-2


def four_chips(out: pathlib.Path, full_size: bool = True) -> None:
    base = ["--arch", ARCH, "--steps", str(TRAIN_STEPS), "--batch",
            str(UNSEEN[0]), "--seq", str(UNSEEN[1])]
    base += ["--full-size"] if full_size else []
    sharded = launch(base + ["--data", "2", "--model-par", "2"], out,
                     "sharded")
    gc.collect()
    single = launch(base, out, "single")
    for a, b in zip(sharded, single, strict=True):
        rtol = STEP0_RTOL if a["step"] == 0 else LATER_RTOL
        rel = abs(a["loss"] - b["loss"]) / abs(b["loss"])
        print(f"[smoke] step {a['step']}: loss 2x2 mesh {a['loss']!r} "
              f"one device {b['loss']!r} relative diff {rel!r} "
              f"(bound {rtol})", flush=True)
        if a["step"] != b["step"] or not rel <= rtol:
            raise AssertionError(f"losses disagree at step {a['step']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded launcher against one device")
    args = ap.parse_args(argv)

    use_compile_cache()
    device = phase("device", check_device, 4 if args.four_chips else None)
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    cfg = get_config(ARCH)
    if args.four_chips:
        phase("four chips", four_chips, OUT)
    else:
        phase("kernels", check_kernels, cfg, get_config("mamba2-370m"),
              get_config("moonlight-16b-a3b"), *UNSEEN)
        records = phase("profile", profile, cfg, PROFILED, FAST_NETS)
        predictor = phase("serve", fit_and_serve, records, cfg, PROFILED,
                          UNSEEN, OUT)
        phase("train", train, predictor, *UNSEEN, OUT)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
