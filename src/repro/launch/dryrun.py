"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

MUST be the very first statements — before any other import (jax locks the
device count and platform at first init). The fake devices are host
devices, so the platform is pinned to the CPU: on a machine with a chip,
a dry run (and every child of ``repro.launch.sweep``) never takes it.
"""
import os  # noqa: E402
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis import roofline as rl  # noqa: E402
from repro.configs import SHAPES, get_config, list_archs, shape_applicable  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import engine  # noqa: E402
from repro.train import optimizer as opt_lib  # noqa: E402
from repro.train import step as step_lib  # noqa: E402


def input_specs(arch: str, shape_name: str = "train_4k") -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell.

    Weak-type-correct, shardable, no device allocation. Training cells get
    {tokens, labels}; prefill cells {tokens}; decode cells {tokens, pos}.
    Modality frontends are stubs: VLM cells add precomputed patch
    embeddings, audio cells add precomputed frame embeddings.
    """
    cfg = get_config(arch)
    shp = SHAPES[shape_name]
    b = shp.global_batch
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    if shp.kind == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((b, shp.seq_len), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((b, shp.seq_len), jnp.int32)}
    elif shp.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, shp.seq_len), jnp.int32)}
    else:  # decode: one new token against a seq_len cache
        specs = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                 "pos": jax.ShapeDtypeStruct((b,), jnp.int32)}
    if cfg.cross_every and shp.kind != "decode":
        specs["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.vision_seq, cfg.d_model), dt)
    if cfg.is_encoder_decoder and shp.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.audio_seq, cfg.d_model), dt)
    return specs


# Giant archs: parameters FSDP-shard over (pod, data) in addition to
# model-axis TP (arctic's 477B cannot fit at TP-16), and arctic trains
# masterless (pure-bf16 AdamW with stochastic rounding on TPU): master
# fp32 alone would be 477e9*4/256 = 7.5 GiB/device.
FSDP_PARAMS = {"arctic-480b", "llama-3.2-vision-90b"}
NO_MASTER = {"arctic-480b"}
# Gradient accumulation (microbatching) for train cells whose activation
# working set exceeds HBM at full batch — the standard production lever.
# (dp-scheme models excluded: their grad accumulators are replicated
# fp32 trees, so accumulation *adds* memory — measured in §Perf)
TRAIN_ACCUM = {"llama-3.2-vision-90b": 4, "arctic-480b": 4,
               "qwen2.5-32b": 4, "jamba-v0.1-52b": 4,
               "moonlight-16b-a3b": 2}


def _lower_cell(arch: str, shape_name: str, mesh, rules: shd.ShardingRules,
                opt_cfg: Optional[opt_lib.OptConfig] = None,
                donate: bool = True, scheme: str = "sp"):
    cfg = get_config(arch)
    shp = SHAPES[shape_name]
    rules = shd.scheme_rules(scheme, rules)
    fsdp = arch in FSDP_PARAMS
    model = build_model(cfg, sharder=shd.make_sharder(mesh, rules, scheme))
    specs = input_specs(arch, shape_name)
    batch_sh = step_lib.batch_shardings(mesh, specs, rules)

    if shp.kind == "train":
        opt_cfg = opt_cfg or opt_lib.OptConfig(
            moment_dtype="bfloat16", keep_master=arch not in NO_MASTER)
        fn = step_lib.make_train_step(model, opt_cfg,
                                      accum=TRAIN_ACCUM.get(arch, 1))
        state_shape = step_lib.state_shapes(model, opt_cfg)
        state_sh = step_lib.state_shardings(model, opt_cfg, mesh, rules,
                                            scheme=scheme, fsdp_params=fsdp)
        jf = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                     donate_argnums=(0,) if donate else ())
        with mesh:
            lowered = jf.lower(state_shape, specs)
    elif shp.kind == "prefill":
        fn = engine.make_prefill_step(model)
        p_sh = engine.param_shardings(model, mesh, rules, fsdp_params=fsdp)
        c_sh, _ = engine.cache_shardings(model, mesh, shp.global_batch,
                                         shp.seq_len, rules)
        jf = jax.jit(fn, in_shardings=(p_sh, batch_sh),
                     out_shardings=(NamedSharding(mesh, P()), c_sh))
        with mesh:
            lowered = jf.lower(model.init_shape(), specs)
    else:  # decode
        fn = engine.make_decode_step(model)
        p_sh = engine.param_shardings(model, mesh, rules, fsdp_params=fsdp)
        c_sh, c_shape = engine.cache_shardings(model, mesh, shp.global_batch,
                                               shp.seq_len, rules)
        jf = jax.jit(fn,
                     in_shardings=(p_sh, c_sh, batch_sh["tokens"],
                                   batch_sh["pos"]),
                     donate_argnums=(1,) if donate else ())
        with mesh:
            lowered = jf.lower(model.init_shape(), c_shape,
                               specs["tokens"], specs["pos"])
    return model, lowered


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                rules: Optional[shd.ShardingRules] = None,
                verbose: bool = True, scheme: str = "sp",
                service=None) -> Dict:
    """Lower + compile one cell; return roofline record (§Dry-run/§Roofline).

    ``service`` is anything with ``predict_one(cfg, batch, seq)`` — a
    ``PredictionService`` or the micro-batched ``AbacusServer`` gateway.
    Train cells then carry the DNNAbacus (predicted) step time/memory
    next to the roofline numbers; repeated sweeps hit the trace cache,
    and with a ``TraceStore`` behind it, fresh processes warm-start.
    """
    cfg = get_config(arch)
    shp = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shp)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}
    rules = rules or shd.ShardingRules()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size

    t0 = time.time()
    model, lowered = _lower_cell(arch, shape_name, mesh, rules, scheme=scheme)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    roof = rl.analyze(compiled)
    mflops = rl.model_flops(cfg, shp, model.param_count(),
                            model.param_count(active_only=True)) / n_dev
    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "devices": n_dev, "status": "ok", "scheme": scheme,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        **roof.summary(mflops),
    }
    if service is not None and shp.kind == "train":
        # the estimate is an optional annotation: never let a predictor
        # failure overwrite a successfully compiled cell's record
        try:
            est = service.predict_one(cfg, shp.global_batch, shp.seq_len)
            rec["abacus_time_s"] = round(est["time_s"], 4)
            rec["abacus_memory_gib"] = round(est["memory_bytes"] / 2**30, 3)
            if "generation" in est:
                rec["abacus_generation"] = est["generation"]
            # feed the compile-time ground truth we DO have back into the
            # refit loop: roofline-bound step time and XLA peak bytes are
            # measured proxies for the job's realized cost, so a dry-run
            # sweep doubles as a calibration pass over every train cell.
            observe = getattr(service, "observe", None)
            if observe is not None:
                t_roof = max(roof.t_compute, roof.t_memory,
                             roof.t_collective)
                observe(cfg, shp.global_batch, shp.seq_len,
                        float(t_roof), rec["peak_hbm_gib"] * 2**30,
                        predicted_time_s=est["time_s"],
                        predicted_mem_bytes=est["memory_bytes"],
                        generation=est.get("generation"),
                        job_id=f"dryrun:{arch}:{shape_name}")
        except Exception as e:
            rec["abacus_error"] = f"{type(e).__name__}: {e}"[:200]
    if verbose:
        ma = compiled.memory_analysis()
        print(f"[dryrun] {arch} x {shape_name} mesh={mesh.devices.shape}")
        print(f"  memory_analysis: args={rec['argument_gib']:.2f}GiB "
              f"temp={rec['temp_gib']:.2f}GiB peak={rec['peak_hbm_gib']:.2f}GiB")
        print(f"  cost_analysis: flops/dev={roof.flops:.3e} "
              f"bytes/dev={roof.bytes_accessed:.3e}")
        print(f"  collectives: { {k: int(v['count']) for k, v in roof.collectives.items()} }")
        print(f"  roofline: compute={roof.t_compute*1e3:.2f}ms "
              f"memory={roof.t_memory*1e3:.2f}ms "
              f"collective={roof.t_collective*1e3:.2f}ms "
              f"-> bound by {roof.bottleneck}")
        print(f"  model_flops/dev={mflops:.3e} useful={rec['useful_flop_fraction']:.3f} "
              f"mfu_bound={rec.get('mfu_bound', 0):.3f}")
        del ma
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--scheme", default="sp", help="sp | sp_heads | tp")
    ap.add_argument("--predict", action="store_true",
                    help="attach DNNAbacus estimates to train cells")
    ap.add_argument("--predictor-path", default="artifacts/abacus")
    ap.add_argument("--trace-store", default="artifacts/trace_store",
                    help="persistent trace dir ('' disables): repeated "
                         "dry-runs warm-start instead of re-tracing")
    ap.add_argument("--feedback-store", default="artifacts/feedback_store",
                    help="persistent measured-cost observations ('' "
                         "disables): each predicted train cell's roofline "
                         "time / peak HBM feed the online-refit loop")
    ap.add_argument("--replicas", type=int, default=1,
                    help="gateway replicas: > 1 serves estimates from a "
                         "fingerprint-sharded ClusterFrontend (per-replica "
                         "trace/feedback slices under the store paths)")
    ap.add_argument("--resize-to", type=int, default=0,
                    help="live-reshard the fleet to this many replicas "
                         "after the first arch (drain -> migrate -> "
                         "cutover under the sweep's own load; requires "
                         "--replicas > 1)")
    ap.add_argument("--rpc", action="store_true",
                    help="with --replicas > 1: spawn each gateway as its "
                         "own process (python -m repro.serve.rpc) behind "
                         "the TCP frame transport; a crashed replica is "
                         "auto-excluded and its warm slice rebuilt from "
                         "disk by the surviving owners")
    ap.add_argument("--store-backend", default=None,
                    choices=("json", "segment"),
                    help="physical layout for the trace/feedback stores "
                         "(default: REPRO_STORE_BACKEND env var, else "
                         "json); exported to RPC children so every "
                         "process reads one layout")
    ap.add_argument("--metrics-out", default=None,
                    help="with --predict: write the serving metrics "
                         "snapshot here at sweep end (.prom/.txt -> "
                         "Prometheus text exposition, else JSON)")
    ap.add_argument("--events-out", default=None,
                    help="with --predict: append structured JSONL "
                         "lifecycle events (gen swaps, reshards, "
                         "exclusions, refits) to this file; RPC children "
                         "append to the same file")
    args = ap.parse_args(argv)

    service = server = None
    rpc_fleet = []
    if args.rpc and args.replicas < 2:
        print("[dryrun] --rpc needs a fleet (--replicas > 1); serving "
              "in-process", file=sys.stderr)
        args.rpc = False
    if args.store_backend:
        # one env var selects the layout everywhere: the factories below
        # read it, and spawned RPC children inherit it
        os.environ["REPRO_STORE_BACKEND"] = args.store_backend
    if args.predict:
        from repro.core.predictor import DNNAbacus
        from repro.obs import events
        from repro.serve.feedback_store import make_feedback_store
        from repro.serve.server import AbacusServer
        from repro.serve.trace_store import make_trace_store
        if args.events_out:
            # O_APPEND one-line writes: RPC children share the same file
            events.configure(path=args.events_out)
        if os.path.exists(args.predictor_path + ".json"):
            if args.rpc:
                # process-separated fleet: each gateway is its own
                # ``python -m repro.serve.rpc`` child; the frontend
                # routes over TCP and keeps LOCAL store handles on the
                # same slice directories (shared disk), so exclusion
                # and migration work exactly as in-process.
                from repro.serve.cluster import ClusterFrontend
                from repro.serve.rpc import spawn_replica, shutdown_fleet
                try:
                    for i in range(args.replicas):
                        name = f"r{i}"
                        rpc_fleet.append(spawn_replica(
                            name, args.predictor_path,
                            trace_root=(os.path.join(args.trace_store, name)
                                        if args.trace_store else None),
                            feedback_root=(
                                os.path.join(args.feedback_store, name)
                                if args.feedback_store else None),
                            event_log=args.events_out or None))
                except BaseException:
                    shutdown_fleet(rpc_fleet)
                    raise
                server = ClusterFrontend(replicas=rpc_fleet,
                                         hedge_after_s=5.0).start()
            elif args.replicas > 1:
                # the fleet path: estimates route by config fingerprint
                # to N sharded gateways; each cell's observation lands
                # in the owning replica's feedback slice, ready for a
                # later federated refit pass.
                from repro.serve.cluster import ClusterFrontend
                server = ClusterFrontend(
                    DNNAbacus.load(args.predictor_path),
                    n_replicas=args.replicas,
                    trace_root=args.trace_store or None,
                    feedback_root=args.feedback_store or None).start()
            else:
                store = (make_trace_store(args.trace_store)
                         if args.trace_store else None)
                service = DNNAbacus.load(
                    args.predictor_path).service(store=store)
                feedback = (make_feedback_store(args.feedback_store)
                            if args.feedback_store else None)
                # estimates go through the micro-batched gateway, sharing
                # its trace cache (and store) with any concurrent admission
                # loop; observed cell costs land in the feedback store so a
                # later refit pass (OnlineRefitter) can consume them.
                server = AbacusServer(service, feedback=feedback).start()
        else:
            print(f"[dryrun] no fitted predictor at {args.predictor_path}; "
                  "skipping estimates", file=sys.stderr)

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    resize_to = int(args.resize_to or 0)
    if resize_to and not hasattr(server, "resize"):
        print("[dryrun] --resize-to needs a fleet (--replicas > 1); "
              "ignoring", file=sys.stderr)
        resize_to = 0
    if resize_to and rpc_fleet and resize_to > len(rpc_fleet):
        # growing an RPC fleet means spawning processes, which the
        # reshard recipe (it mints in-process gateways) cannot do
        print("[dryrun] --resize-to growth is not supported with --rpc; "
              "ignoring", file=sys.stderr)
        resize_to = 0
    failures = 0
    try:
        for arch in archs:
            for shape_name in shapes:
                for mp in meshes:
                    try:
                        rec = dryrun_cell(arch, shape_name, multi_pod=mp,
                                          scheme=args.scheme,
                                          service=server or service)
                    except Exception as e:  # a failure here is a sharding bug
                        rec = {"arch": arch, "shape": shape_name,
                               "multi_pod": mp, "status": "FAILED",
                               "error": f"{type(e).__name__}: {e}"}
                        failures += 1
                        print(f"[dryrun] FAILED {arch} x {shape_name} mp={mp}: "
                              f"{rec['error'][:500]}", file=sys.stderr)
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(rec) + "\n")
            if resize_to:
                # live reshard mid-sweep: remaining cells exercise the
                # post-cutover fleet (warm slices migrated, not retraced)
                mig = server.resize(resize_to)
                print(f"[dryrun] resharded fleet {len(mig['from'])} -> "
                      f"{len(mig['to'])} replicas: {mig['keys_moved']} keys "
                      f"moved ({mig['moved_fraction_bound']:.0%} of keyspace; "
                      f"naive rehash = 100%), {mig['cutover_ticks']} drain "
                      "ticks", file=sys.stderr)
                resize_to = 0
    finally:
        if server is not None:
            # works for both the single gateway and the cluster frontend
            # (whose calibration is the count-weighted fleet merge)
            cal = server.stats()["calibration"]
            if cal["count"]:
                print(f"[dryrun] calibration over {cal['count']} cells: "
                      f"time_mre={cal['time_mre']:.3f} "
                      f"time_drift={cal['time_drift']:+.3f} "
                      f"mem_mre={cal['mem_mre']:.3f}", file=sys.stderr)
            reshard = getattr(server, "reshard_stats", None)
            if reshard and reshard["reshards"]:
                print(f"[dryrun] reshards={reshard['reshards']} "
                      f"keys_moved={reshard['keys_moved']} "
                      f"replayed={reshard['keys_replayed']}", file=sys.stderr)
            if args.metrics_out:
                # snapshot BEFORE stop(): a fleet frontend fetches each
                # replica's registry over RPC, so the fleet must be alive
                try:
                    if args.metrics_out.endswith((".prom", ".txt")):
                        body = server.metrics_text()
                    else:
                        body = json.dumps(server.metrics_snapshot(),
                                          indent=2, sort_keys=True)
                    with open(args.metrics_out, "w") as f:
                        f.write(body + "\n")
                    print(f"[dryrun] metrics snapshot -> {args.metrics_out}",
                          file=sys.stderr)
                except Exception as e:
                    print(f"[dryrun] metrics snapshot failed: "
                          f"{type(e).__name__}: {e}", file=sys.stderr)
            server.stop()
        if rpc_fleet:
            from repro.serve.rpc import shutdown_fleet
            shutdown_fleet(rpc_fleet)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
