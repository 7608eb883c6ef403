"""The program's own spans and named scopes in a profiler trace.

An addition to ``xtrace.py``, which it leaves as it is. ``load`` keeps
what ``xtrace.load`` keeps, and also

- the program's host spans, the events whose names start with one of
  ``PROGRAM_PREFIXES`` (``loader/wait``, ``step/dispatch``, ...), each on
  the line of the thread that opened it;
- for each device op, its scope: the innermost of ``SCOPES`` on the op's
  ``op_name`` path (``jit(train_step)/transpose(jvp(mlp))/dot_general``
  is ``mlp``), or ``other``. The ``op_name`` comes from the compiled
  program's HLO text, joined to the op by its instruction name, as a
  device line's ``scopes`` list beside its ``events`` (a trace taken
  before the program had scopes has none: all its ops are ``other``).
  A TPU trace has no name-scope line, and holds the ``op_name`` only as
  a ``tf_op`` stat of each op's metadata, which
  ``jax.profiler.ProfileData`` does not show.

``reduce`` returns ``xtrace.reduce``'s numbers, computed by it on the
benchmark's own spans alone (so ``busy_s``, ``window_s`` and
``device_ops`` are the same numbers), and adds

- ``spans``: per program span, its count and its host seconds inside the
  window, on every thread;
- ``scopes``: device self time per scope inside the window, averaged
  over the devices;
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
  ``step/retry`` spans of that step.

Run by hand on the chip, a traced run of one cell with this reduction
(the benchmark's result line is ``run.py``'s)::

    python3 -m chipbench.xscope --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import argparse
import gzip
import json
import re
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Sequence, Tuple

from chipbench import xtrace

T0 = time.perf_counter()

PROGRAM_PREFIXES = ("loader/", "step/", "ckpt/")
SCOPES = ("embed", "attention", "mlp", "moe", "ssm", "norm", "lm_head", "optimizer")
OTHER = "other"
# per step, in ms: ``<scope>_ms.train`` for these scopes, and the two spans
STEP_SCOPES = ("attention", "mlp", "norm", "lm_head", "embed", "optimizer")
STEP_SPANS = {"loader_wait_ms.train": "loader/wait",
              "dispatch_ms.train": "step/dispatch"}

_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
                      re.M)


def scope_of(op_name: str) -> str:
    """The innermost scope on an ``op_name`` path, or ``other``."""
    for tok in reversed(re.findall(r"[^/()]+", op_name)):
        if tok in SCOPES:
            return tok
    return OTHER


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name to ``op_name`` for each instruction that has one."""
    return {m.group(1): m.group(2) for m in _OP_NAME.finditer(hlo_text)}


def _is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def load(path: str, hlo_text: str) -> xtrace.Planes:
    import jax
    names = op_names(hlo_text)
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != xtrace.OPS_LINE:
                continue
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in line.events]
            if device:
                keep = {"events": evs, "scopes": [
                    scope_of(names.get(xtrace.op_name(n).split(" ")[0], ""))
                    for n, _, _ in evs]}
            else:
                keep = {"events": [e for e in evs if e[0] in xtrace.HOST_SPANS
                                   or _is_program(e[0])]}
            if keep["events"]:
                lines.append({"name": line.name, **keep})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _benchmark_view(planes: xtrace.Planes) -> xtrace.Planes:
    """The planes as ``xtrace.load`` gives them: no program span."""
    out = []
    for p in planes:
        lines = p["lines"]
        if p["name"].startswith("/host:"):
            lines = [{"name": ln["name"], "events": [
                e for e in ln["events"] if e[0] in xtrace.HOST_SPANS]} for ln in lines]
        out.append({"name": p["name"], "lines": lines})
    return out


def _window(planes: xtrace.Planes) -> Tuple[float, float, List[List]]:
    """The window and the events of the host line that opened it."""
    for p in planes:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                for n, s, d in ln["events"]:
                    if n == "window":
                        return s, s + d, ln["events"]
    raise ValueError("the trace holds no host span named 'window'")


def _name_at(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    around = [x for x in spans if x[1] <= t <= x[2]]
    program = [x for x in around if _is_program(x[0])]
    pick = program or around
    return min(pick, key=lambda x: x[2] - x[1])[0] if pick else "host"


def reduce(planes: xtrace.Planes, top: int = 10, long_s: float = 0.05) -> Dict[str, Any]:
    out = xtrace.reduce(_benchmark_view(planes), top)
    w0, w1, thread = _window(planes)
    mine = [(n, s, s + d) for n, s, d in thread
            if n != "window" and s + d > w0 and s < w1]

    spans: Dict[str, Dict[str, float]] = {}
    for p in planes:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                for n, s, d in ln["events"]:
                    clip = min(s + d, w1) - max(s, w0)
                    if _is_program(n) and clip > 0:
                        acc = spans.setdefault(n, {"count": 0, "s": 0.0})
                        acc["count"] += 1
                        acc["s"] += clip * 1e-9

    devices = [p for p in planes if p["name"].startswith("/device:")
               and any(ln["events"] for ln in p["lines"])]
    scopes: Dict[str, float] = {}
    gaps: List[Tuple[str, float, float]] = []  # name, start, end (ns)
    for p in devices:
        iv = []
        for ln in p["lines"]:
            scoped = ln.get("scopes") or [OTHER] * len(ln["events"])
            clipped = [(sc, max(s, w0), min(s + d, w1))
                       for (_, s, d), sc in zip(ln["events"], scoped)]
            clipped = [x for x in clipped if x[2] > x[1]]
            iv += [(s, e) for _, s, e in clipped]
            for sc, t in xtrace._self_times(clipped):
                scopes[sc] = scopes.get(sc, 0.0) + t * 1e-9 / len(devices)
        edges = [w0] + [x for b in xtrace._union(iv) for x in b] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_name_at((s + e) / 2, mine), s, e))

    split: Dict[str, float] = {}
    for _, s, e in gaps:
        cuts = sorted({s, e} | {x for _, a, b in mine for x in (a, b) if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            name = _name_at((a + b) / 2, mine)
            split[name] = split.get(name, 0.0) + (b - a) * 1e-9 / len(devices)

    trains = [(s, e) for n, s, e in mine if n == "train"]
    long_gaps = []
    gaps.sort(key=lambda g: g[1] - g[2])  # longest first
    for name, s0, e0 in gaps:
        t, mid = (e0 - s0) * 1e-9, (s0 + e0) / 2
        if t < long_s:
            break
        k = next((i for i, (s, e) in enumerate(trains) if s <= mid <= e), None)
        retries = 0 if k is None else sum(
            1 for n, s, _ in mine
            if n == "step/retry" and trains[k][0] <= s <= trains[k][1])
        long_gaps.append({"span": name, "s": t, "step": k, "retries": retries})
    out.update(
        spans=spans, scopes=scopes, long_gaps=long_gaps, idle_split=split,
        idle_gaps=[[n, (e - s) * 1e-9] for n, s, e in gaps[:top]],
        idle_by_span=xtrace._totals([(n, (e - s) * 1e-9) for n, s, e in gaps]))
    return out


def per_step_ms(r: Dict[str, Any], steps: int) -> Dict[str, float]:
    """The window's totals per step, in ms, under the names the benchmark
    would report them by; a scope or span the trace lacks is left out."""
    out = {f"{sc}_ms.train": r["scopes"][sc] * 1e3 / steps
           for sc in STEP_SCOPES if sc in r["scopes"]}
    out.update({m: r["spans"][sp]["s"] * 1e3 / steps
                for m, sp in STEP_SPANS.items() if sp in r["spans"]})
    return out


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
    # the traced run compiles the step once more for its footprint; keep
    # that program's text, whose op_name metadata names each op's scope
    hlo: List[str] = []
    footprint = train_loop.step_footprint

    def keep_text(jstep, args_):
        hlo.append(jstep.lower(*args_).compile().as_text())
        return footprint(jstep, args_)

    train_loop.step_footprint = keep_text
    ctx = SimpleNamespace(workload=cell, config=registry.config(cell["config"]),
                          traffic=traffic, seed=args.seed, seconds=args.seconds,
                          trace=True, trace_dir=str(trace_dir), t0=T0)
    try:
        out = train_loop.run(ctx)
    finally:
        train_loop.step_footprint = footprint
    planes = load(xtrace.find_xplane(str(trace_dir)), hlo[0])
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.keep:
        with gzip.open(args.keep, "wt") as f:
            json.dump(planes, f)
    r = reduce(planes)
    busy = r["busy_s"]
    # the program's recompile events give each window step's compiles
    from repro.obs import events
    first = traffic["check_steps"]
    compiled = {e["step"] - first: e["compiles"] for e in events.tail()
                if e["event"] == "recompile" and e["step"] >= first}
    for g in r["long_gaps"]:
        g["compiles"] = compiled.get(g["step"], 0) if g["step"] is not None else None
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "correct": out["correct"],
        "attempted": out["attempted"], "e2e": out["e2e"], "device": device,
        "compiles_in_window": out["log"]["compiles_in_window"],
        "busy_s": busy, "window_s": r["window_s"],
        "idle_pct": 100.0 * (1.0 - busy / r["window_s"]),
        "per_step_ms": per_step_ms(r, out["attempted"]),
        "scope_share_pct": {k: 100.0 * v / busy for k, v in sorted(r["scopes"].items())},
        "spans": r["spans"], "long_gaps": r["long_gaps"], "idle_split": r["idle_split"],
        "idle_gaps": r["idle_gaps"], "idle_by_span": r["idle_by_span"],
        "device_ops": r["device_ops"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
