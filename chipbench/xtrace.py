"""From a profiler trace to device time by op, by scope and idle.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: planes, their lines, and events as ``[name, start_ns, dur_ns]``
(host and device events share one clock). It keeps

- on the host, the benchmark's spans (``HOST_SPANS``) and the program's,
  named ``<layer>/<what>`` (``loader/wait``, ``step/dispatch``), each on
  the line of the thread that opened it;
- on each device, the ops of the ``XLA Ops`` line and, as the line's
  ``op_names`` list beside its ``events``, each op's ``op_name``: the
  path of transforms and named scopes that made it
  (``jit(train_step)/transpose(jvp(mlp))/dot_general``). It comes from the
  compiled program's HLO text, joined to the op by its instruction name:
  a TPU trace holds it only as a ``tf_op`` stat of each op's metadata,
  which ``jax.profiler.ProfileData`` does not show. A line without the
  list has no scopes.

``reduce`` takes the window from the host span named ``window`` that the
benchmark opens around its measured steps, and gives:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices;
- ``window_s``: the window's length;
- ``ops``: every operation's device time of its own in the window (an
  op's time less the ops nested in it, as a loop's body is in the loop),
  summed by name, the HLO name and the op's result type, averaged over
  the devices; ``device_ops``: the ``top`` longest of them;
- ``scopes``: the same self time summed by every name on the op's
  ``op_name`` path, each name once per op. So a scope is inclusive:
  ``attention`` holds the ops of the ``flash`` scope inside it. The
  transforms' names (``jvp``, ``transpose``) and the primitives' are
  there too; a reader looks up the scope it knows;
- ``spans``: per host span, the benchmark's and the program's, its count
  and its host seconds inside the window, on every thread;
- ``steps``: the window's steps, the benchmark's ``train`` spans that
  open inside it;
- ``idle_gaps``: the longest stretches with no operation on the device,
  each named by the innermost benchmark span the host was in at its
  middle (``host`` where it was in none), and ``idle_by_span`` their
  totals by name.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

Planes = List[Dict[str, Any]]
OPS_LINE = "XLA Ops"
HOST_SPANS = ("data", "dispatch", "step", "train", "window")
STEP_SPAN = "train"
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*/[a-z0-9_/]+$")

_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
                      re.M)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name to ``op_name`` for each instruction that has one."""
    return {m.group(1): m.group(2) for m in _OP_NAME.finditer(hlo_text)}


def scope_names(op_name: str) -> FrozenSet[str]:
    """Every name on an ``op_name`` path, transforms' and scopes' alike."""
    return frozenset(re.findall(r"[^/()]+", op_name))


def load(path: str, hlo_text: str = "") -> Planes:
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
            if device and line.name != OPS_LINE:
                continue
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in line.events]
            if device:
                keep = {"events": evs, "op_names": [
                    names.get(op_name(n).split(" ")[0], "") for n, _, _ in evs]}
            else:
                keep = {"events": [e for e in evs if e[0] in HOST_SPANS
                                   or PROGRAM_SPAN.match(e[0])]}
            if keep["events"]:
                lines.append({"name": line.name, **keep})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window(planes: Planes) -> Tuple[float, float, List[List]]:
    """The window, and the events of the host line that opened it."""
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                for n, s, d in line["events"]:
                    if n == "window":
                        return s, s + d, line["events"]
    raise ValueError("the trace holds no host span named 'window'")


def devices(planes: Planes) -> Planes:
    out = [p for p in planes if p["name"].startswith("/device:")
           and any(line["events"] for line in p["lines"])]
    if not out:
        raise ValueError("the trace holds no device operation")
    return out


def clipped(line: Dict[str, Any], w0: float, w1: float) -> List[Tuple[str, float, float, str]]:
    """A device line's ops inside the window: (name, start, end, op_name)."""
    paths = line.get("op_names") or [""] * len(line["events"])
    out = [(n, max(s, w0), min(s + d, w1), path)
           for (n, s, d), path in zip(line["events"], paths)]
    return [x for x in out if x[2] > x[1]]


def idle(busy: Sequence[Tuple[float, float]], w0: float,
         w1: float) -> List[Tuple[float, float]]:
    """The stretches of the window outside the busy intervals."""
    edges = [w0] + [x for b in busy for x in b] + [w1]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def reduce(planes: Planes, top: int = 10) -> Dict[str, Any]:
    w0, w1, _ = window(planes)
    host = [(n, s, s + d) for p in planes if p["name"].startswith("/host:")
            for line in p["lines"] for n, s, d in line["events"]]
    inner = [x for x in host if x[0] in HOST_SPANS and x[0] != "window"
             and x[2] > w0 and x[1] < w1]
    devs = devices(planes)
    busy_total, op_time, scope_time = 0.0, {}, {}
    parsed: Dict[str, FrozenSet[str]] = {}  # op_name -> its names
    gaps: List[Tuple[str, float]] = []
    for p in devs:
        iv = []
        for line in p["lines"]:
            ops = clipped(line, w0, w1)
            iv += [(s, e) for _, s, e, _ in ops]
            for (n, _, _, path), t in _self_times(ops):
                name = op_name(n)
                op_time[name] = op_time.get(name, 0.0) + t
                if path not in parsed:
                    parsed[path] = scope_names(path)
                for sc in parsed[path]:
                    scope_time[sc] = scope_time.get(sc, 0.0) + t
        busy = _union(iv)
        busy_total += sum(e - s for s, e in busy)
        for s, e in idle(busy, w0, w1):
            mid = (s + e) / 2
            around = [x for x in inner if x[1] <= mid <= x[2]]
            name = min(around, key=lambda x: x[2] - x[1])[0] if around else "host"
            gaps.append((name, (e - s) * 1e-9))
    spans: Dict[str, Dict[str, float]] = {}
    for n, s, e in host:
        clip = min(e, w1) - max(s, w0)
        if clip > 0:
            acc = spans.setdefault(n, {"count": 0, "s": 0.0})
            acc["count"] += 1
            acc["s"] += clip * 1e-9
    k = len(devs)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_total * 1e-9 / k,
        "window_s": (w1 - w0) * 1e-9,
        "devices": k,
        "device_ops": [[n, t * 1e-9 / k] for n, t in ops[:top]],
        "ops": {n: t * 1e-9 / k for n, t in ops},
        "scopes": {n: t * 1e-9 / k for n, t in scope_time.items()},
        "spans": spans,
        "steps": sum(1 for n, s, _ in host if n == STEP_SPAN and w0 <= s < w1),
        "idle_gaps": [[n, t] for n, t in sorted(gaps, key=lambda g: -g[1])[:top]],
        "idle_by_span": _totals(gaps),
    }


def scope_ms(trace: Optional[Dict[str, Any]], scope: str) -> Optional[float]:
    """A scope's device ms per window step, or None where it has none."""
    if not trace or scope not in trace["scopes"] or not trace["steps"]:
        return None
    return trace["scopes"][scope] * 1e3 / trace["steps"]


def op_name(text: str) -> str:
    """``%fusion.5 = bf16[8,1024]{1,0:T(8,128)} fusion(...)`` -> ``fusion.5 bf16[8,1024]``."""
    head, _, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    kind = rest.split(" ", 1)[0].split("{", 1)[0].lstrip("(").rstrip(",")
    return f"{name} {kind}" if kind and "[" in kind else name


def _self_times(events: Sequence[Tuple]) -> List[Tuple[Tuple, float]]:
    """Each event ``(name, start, end, ...)`` with its duration less the
    events nested inside it."""
    out: List[Tuple[Tuple, float]] = []
    stack: List[List] = []  # [event, end, own time]
    for ev in sorted(events, key=lambda x: (x[1], -x[2])):
        s, e = ev[1], ev[2]
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([ev, e, e - s])
    out += [(ev, t) for ev, _, t in stack]
    return out


def _totals(gaps: Sequence[Tuple[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, t in gaps:
        out[n] = out.get(n, 0.0) + t
    return out
