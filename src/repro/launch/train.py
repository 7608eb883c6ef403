"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the reduced smoke config by default and the published widths with
``--full-size``, on the local devices: one, or a ``--data`` x
``--model-par`` mesh. With ``--predict``, DNNAbacus admission control
estimates step time and peak memory for the requested config *before*
allocating anything and refuses jobs predicted to exceed the memory the
devices report — the paper's scheduling application wired into the
launcher.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (default: reduced smoke size)")
    ap.add_argument("--data", type=int, default=1, help="data-parallel axis")
    ap.add_argument("--model-par", type=int, default=1, help="model axis")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--no-zero", action="store_true")
    ap.add_argument("--predict", action="store_true",
                    help="DNNAbacus admission control before launch")
    ap.add_argument("--predictor-path", default="artifacts/abacus")
    ap.add_argument("--log", default=None)
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_config, reduced_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.train import optimizer as opt_lib
    from repro.train.loop import LoopConfig, Trainer
    from repro.distributed import sharding as shd

    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduced_config(cfg)
    mesh = (make_host_mesh(args.data, args.model_par)
            if args.data * args.model_par > 1 else None)
    model = build_model(cfg, sharder=shd.make_sharder(mesh))

    predicted = None
    if args.predict:
        from repro.core.predictor import DNNAbacus
        if os.path.exists(args.predictor_path + ".json"):
            service = DNNAbacus.load(args.predictor_path).service()
            est = service.predict_one(cfg, args.batch, args.seq)
            predicted = est["time_s"]
            print(f"[abacus] predicted step time {est['time_s']*1e3:.1f} ms, "
                  f"peak memory {est['memory_bytes']/2**30:.2f} GiB")
            devices = (list(mesh.devices.flat) if mesh is not None
                       else jax.devices()[:1])
            limits = [(d.memory_stats() or {}).get("bytes_limit")
                      for d in devices]
            if None in limits:
                print(f"[abacus] {devices[0].platform} reports no memory "
                      "limit; admission not checked", file=sys.stderr)
            elif est["memory_bytes"] > sum(limits):
                print("[abacus] REFUSED: predicted OOM "
                      f"({est['memory_bytes']/2**30:.2f} GiB > "
                      f"{sum(limits)/2**30:.2f} GiB on {len(devices)} "
                      f"{devices[0].device_kind})", file=sys.stderr)
                return 2
        else:
            print("[abacus] no fitted predictor found; run "
                  "benchmarks/bench_mre.py or examples/predict_and_schedule.py "
                  "first", file=sys.stderr)

    loop_cfg = LoopConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                          ckpt_dir=args.ckpt_dir, accum=args.accum,
                          zero=not args.no_zero,
                          predicted_step_s=predicted)
    trainer = Trainer(model, opt_lib.OptConfig(), loop_cfg, mesh=mesh)
    log = trainer.run()
    for rec in log:
        print(json.dumps(rec))
    if args.log:
        trainer.write_log(args.log)
    print(f"final loss: {log[-1]['loss']:.4f} "
          f"(retries={trainer.runner.retries}, "
          f"stragglers={trainer.runner.stragglers}, "
          f"compiles={trainer.runner.compiles})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
