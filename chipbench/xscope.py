"""A traced run of one cell, read by hand: scopes, spans, and idle time
named by the program's own spans.

    python3 -m chipbench.xscope --workload <cell> --seed <n> --seconds <s> [--keep PATH]

The trace is loaded and reduced as ``run.py`` reduces it
(``xtrace.load``, ``xtrace.reduce``); ``--keep`` writes the loaded planes
(``.json.gz``). Besides the reduction's numbers it prints device ms per
window step for each name on the ops' ``op_name`` paths that holds at
least ``MIN_SHARE`` of busy time, and ``gaps`` names the idle time by
what the program's host was doing:

- ``idle_gaps`` and ``idle_by_span`` named by the innermost program span
  the window's thread was in at the gap's middle, else by the innermost
  benchmark span, else ``host``; spans of other threads (the loader's
  producer) never name a gap;
- ``idle_split``: the idle time cut at the window thread's span edges,
  each piece put down to the span it lies in, by the same rule (a gap
  outlives the span at its middle: the host returns from ``step/block``
  after the device ends a step, and the device starts the next one
  inside ``step/dispatch``);
- ``long_gaps``: every gap of ``long_s`` or more, with its span, the
  window step (``train`` span) it fell in, counted from 0, and the
  ``step/retry`` spans of that step (the benchmark's window calls the
  compiled step without ``StepRunner``, so its traces have none; the
  window's compiles are ``compiles_in_window``).
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Sequence, Tuple

from chipbench import xtrace
from chipbench.xtrace import op_names  # noqa: F401  (the join, for by-hand profiles)

T0 = time.perf_counter()
MIN_SHARE = 0.001  # of busy time: the scopes printed


def _is_program(name: str) -> bool:
    return xtrace.PROGRAM_SPAN.match(name) is not None


def _name_at(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    around = [x for x in spans if x[1] <= t <= x[2]]
    program = [x for x in around if _is_program(x[0])]
    pick = program or around
    return min(pick, key=lambda x: x[2] - x[1])[0] if pick else "host"


def gaps(planes: xtrace.Planes, top: int = 10, long_s: float = 0.05) -> Dict[str, Any]:
    """``idle_gaps``, ``idle_by_span``, ``idle_split`` and ``long_gaps``."""
    w0, w1, thread = xtrace.window(planes)
    mine = [(n, s, s + d) for n, s, d in thread
            if n != "window" and s + d > w0 and s < w1]
    devs = xtrace.devices(planes)
    found: List[Tuple[str, float, float]] = []  # name, start, end (ns)
    for p in devs:
        iv = [(s, e) for line in p["lines"]
              for _, s, e, _ in xtrace.clipped(line, w0, w1)]
        for s, e in xtrace.idle(xtrace._union(iv), w0, w1):
            found.append((_name_at((s + e) / 2, mine), s, e))

    split: Dict[str, float] = {}
    for _, s, e in found:
        cuts = sorted({s, e} | {x for _, a, b in mine for x in (a, b) if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            name = _name_at((a + b) / 2, mine)
            split[name] = split.get(name, 0.0) + (b - a) * 1e-9 / len(devs)

    trains = [(s, e) for n, s, e in mine if n == xtrace.STEP_SPAN]
    long_gaps = []
    found.sort(key=lambda g: g[1] - g[2])  # longest first
    for name, s0, e0 in found:
        t, mid = (e0 - s0) * 1e-9, (s0 + e0) / 2
        if t < long_s:
            break
        k = next((i for i, (s, e) in enumerate(trains) if s <= mid <= e), None)
        retries = 0 if k is None else sum(
            1 for n, s, _ in mine
            if n == "step/retry" and trains[k][0] <= s <= trains[k][1])
        long_gaps.append({"span": name, "s": t, "step": k, "retries": retries})
    return {"long_gaps": long_gaps, "idle_split": split,
            "idle_gaps": [[n, (e - s) * 1e-9] for n, s, e in found[:top]],
            "idle_by_span": xtrace._totals([(n, (e - s) * 1e-9) for n, s, e in found])}


def main(argv: Optional[List[str]] = None) -> int:
    from chipbench import registry, run
    from chipbench.drivers import train_loop

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="also write the loaded planes here (.json.gz)")
    args = ap.parse_args(argv)

    cell = registry.workload(args.workload)
    device = run.require_chip(cell["chips"])
    run.use_compile_cache()
    traffic = registry.traffic(cell["traffic"])
    if traffic["driver"] != "train_loop":
        raise SystemExit(f"{args.workload}: xscope reads train_loop cells only")
    trace_dir = run.WORK_DIR / "xscope" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = SimpleNamespace(workload=cell, config=registry.config(cell["config"]),
                          traffic=traffic, seed=args.seed, seconds=args.seconds,
                          trace=True, trace_dir=str(trace_dir), t0=T0)
    out = train_loop.run(ctx)
    planes = xtrace.load(xtrace.find_xplane(str(trace_dir)), out["hlo_text"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.keep:
        with gzip.open(args.keep, "wt") as f:
            json.dump(planes, f)
    r = xtrace.reduce(planes)
    g = gaps(planes)
    busy = r["busy_s"]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "correct": out["correct"],
        "attempted": out["attempted"], "e2e": out["e2e"], "device": device,
        "compiles_in_window": out["log"]["compiles_in_window"],
        "busy_s": busy, "window_s": r["window_s"], "steps": r["steps"],
        "idle_pct": 100.0 * (1.0 - busy / r["window_s"]),
        "scope_ms_per_step": {k: xtrace.scope_ms(r, k) for k, v in sorted(
            r["scopes"].items(), key=lambda kv: -kv[1]) if v >= MIN_SHARE * busy},
        "spans": r["spans"], **g, "device_ops": r["device_ops"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
