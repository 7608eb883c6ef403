"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name (``registry.py``). The run checks
that JAX sees enough TPU chips (and exits non-zero with no result where
it does not), keeps JAX's compilation cache inside the checkout, hands
the cell to its traffic driver, and prints one JSON object as the last
line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with its limit, which also close
standard error.

A per-layer metric's reader (``metrics/<name>.py``) gets the traffic driver's
outputs with the cell's ``config`` and ``traffic``, the run's
``device_kind``, and ``trace``: the profiler trace joined to the
compiled step's op names and reduced by ``xtrace.reduce`` (device time
by op and by scope, the program's spans, the window's steps, idle time).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent
# Run as a script, Python puts this directory first on the path, where its
# modules would shadow others; the package is imported from the checkout.
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(REPO))

from chipbench import registry, xtrace  # noqa: E402

WORK_DIR = REPO / ".chipbench"  # traces of ``--trace 1`` runs, removed after reading


def require_chip(chips: int) -> Dict[str, Any]:
    """The device JAX runs on; exits non-zero unless it is ``chips`` TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: needs {chips} TPU chip(s), JAX has "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def use_compile_cache() -> None:
    """The program's persistent compilation cache, for every program."""
    sys.path.insert(0, str(REPO / "src"))
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def per_layer(cell: str, out: Dict[str, Any], bench: Dict[str, Any],
              root: pathlib.Path) -> Dict[str, Dict[str, Any]]:
    e2e = [m["name"] for m in registry.metrics_of(cell, bench["end_to_end"])]
    metrics = {}
    for m in registry.metrics_of(cell, bench["per_layer"], e2e):
        value = registry.metric_reader(m["name"], root)(out)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv: Optional[List[str]] = None, root: pathlib.Path = registry.ROOT,
         repo: pathlib.Path = REPO) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = registry.workload(args.workload, root)
    device = require_chip(cell["chips"])
    use_compile_cache()
    traffic = registry.traffic(cell["traffic"], root)
    trace_dir = WORK_DIR / "trace" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = SimpleNamespace(workload=cell, config=registry.config(cell["config"], root),
                          traffic=traffic, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), trace_dir=str(trace_dir), t0=T0)
    out = registry.driver(traffic["driver"]).run(ctx)
    out.update(config=ctx.config, traffic=traffic)
    bench = registry.benchmark(repo)

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result: Dict[str, Any] = {"correct": bool(out["correct"]),
                              "attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        planes = xtrace.load(xtrace.find_xplane(str(trace_dir)), out.pop("hlo_text"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["trace"] = xtrace.reduce(planes)
        out["device_kind"] = device["kind"]
        result["metrics"] = per_layer(args.workload, out, bench, root)
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
        print("idle by host span (s): " + json.dumps(out["trace"]["idle_by_span"]),
              file=sys.stderr)
    else:
        names = [m["name"] for m in registry.metrics_of(args.workload,
                                                        bench["end_to_end"])]
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        result["metrics"] = {n: {"value": out["e2e"][n], "unit": units[n]}
                             for n in names}
    result["device"] = device
    result["checks"] = {k: {"value": v if math.isfinite(v) else repr(v), "limit": lim}
                        for k, (v, lim) in out["checks"].items()}

    print("chipbench log: " + json.dumps(out["log"]), file=sys.stderr)
    for k, (v, lim) in out["checks"].items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
