"""From a profiler trace to device busy and idle time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: planes, their lines, and events as ``[name, start_ns, dur_ns]``
(host and device events share one clock). ``reduce`` takes the window
from the host span named ``window`` that the benchmark opens around its
measured steps, and gives:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the
  window, averaged over the devices;
- ``window_s``: the window's length;
- ``device_ops``: the operations that took most device time of their
  own (an op's time less the ops nested in it, as a loop's body is in the
  loop), summed by name: the HLO name and the op's result type;
- ``idle_gaps``: the longest stretches with no operation on the device,
  each named by the innermost benchmark span the host was in at its
  middle (``host`` where it was in none).
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Sequence, Tuple

Planes = List[Dict[str, Any]]
OPS_LINE = "XLA Ops"
HOST_SPANS = ("data", "dispatch", "step", "train", "window")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Planes:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not (plane.name.startswith("/device:") or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name != OPS_LINE:
                continue
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in line.events]
            if plane.name.startswith("/host:"):
                evs = [e for e in evs if e[0] in HOST_SPANS]
            if evs:
                lines.append({"name": line.name, "events": evs})
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


def _host_spans(planes: Planes) -> List[Tuple[str, float, float]]:
    return [(n, s, s + d) for p in planes if p["name"].startswith("/host:")
            for line in p["lines"] for n, s, d in line["events"]]


def reduce(planes: Planes, top: int = 10) -> Dict[str, Any]:
    spans = _host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("the trace holds no host span named 'window'")
    w0, w1 = windows[0]
    inner = [x for x in spans if x[0] != "window" and x[2] > w0 and x[1] < w1]
    devices = [p for p in planes if p["name"].startswith("/device:")
               and any(line["events"] for line in p["lines"])]
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy_total, op_time = 0.0, {}
    gaps: List[Tuple[str, float]] = []
    for p in devices:
        iv = []
        for line in p["lines"]:
            clipped = [(n, max(s, w0), min(s + d, w1)) for n, s, d in line["events"]]
            clipped = [x for x in clipped if x[2] > x[1]]
            iv += [(s, e) for _, s, e in clipped]
            for n, t in _self_times(clipped):
                op_time[n] = op_time.get(n, 0.0) + t
        busy = _union(iv)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for b in busy for x in b] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                around = [x for x in inner if x[1] <= mid <= x[2]]
                name = min(around, key=lambda x: x[2] - x[1])[0] if around else "host"
                gaps.append((name, (e - s) * 1e-9))
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_total * 1e-9 / len(devices),
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(devices),
        "device_ops": [[n, t * 1e-9 / len(devices)] for n, t in ops],
        "idle_gaps": [[n, t] for n, t in sorted(gaps, key=lambda g: -g[1])[:top]],
        "idle_by_span": _totals(gaps),
    }


def op_name(text: str) -> str:
    """``%fusion.5 = bf16[8,1024]{1,0:T(8,128)} fusion(...)`` -> ``fusion.5 bf16[8,1024]``."""
    head, _, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    kind = rest.split(" ", 1)[0].split("{", 1)[0].lstrip("(").rstrip(",")
    return f"{name} {kind}" if kind and "[" in kind else name


def _self_times(events: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float]]:
    """Each event's duration less the events nested inside it, by op name."""
    out: List[Tuple[str, float]] = []
    stack: List[List] = []  # [name, end, own time]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((op_name(done[0]), done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, e - s])
    out += [(op_name(n), t) for n, _, t in stack]
    return out


def _totals(gaps: Sequence[Tuple[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, t in gaps:
        out[n] = out.get(n, 0.0) + t
    return out
