"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the benchmark
reports: device busy time, the traced window, time per XLA module, the
device operations that took most time, and the longest idle gaps (with an
interval, the idle at its two edges among them).

Run as a child pinned to the host CPU (``ProfileData`` lives in JAX):
``python benchmark/lib/xplane.py <trace dir or file> <out.json> [<from_s> <to_s>]``.
The optional interval (seconds from the trace's own start) keeps only the
events that begin inside it — the launcher's two ``/metrics`` scrapes bound
it, so counters and device time cover the same seconds — and is then the
traced window.

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event per executed operation and ``XLA Modules`` one
per executed program; busy time is the union of the ``XLA Ops`` intervals
(of ``XLA Modules`` where a plane has no op line). Averages are over the
device planes that ran anything.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals; the result is sorted and disjoint."""
    out: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def module_key(name: str) -> str:
    """``jit_step(123456789)`` → ``jit_step``: the program's name without
    the per-compilation fingerprint."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_key(name: str) -> str:
    """``%fusion.4 = bf16[16,256,256,64]{3,0,2,1:T(8,128)} fusion(...)`` →
    ``%fusion.4 bf16[16,256,256,64]``: the trace's own name and the output
    shape, without layouts and operands."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:96]
    shape = re.match(r"\(?[a-z0-9]+\[[\d,]*\]", rest)
    return (head + (" " + shape.group(0).lstrip("(") if shape else ""))[:96]


def reduce_planes(planes: list[dict],
                  interval_ns: tuple[int, int] | None = None) -> dict:
    """``planes``: ``[{"name", "lines": {line: [(name, start_ns, dur_ns)]}}]``
    → the summary. Pure arithmetic, tested on hand-made planes."""
    devices, ops, modules = [], {}, {}
    for plane in planes:
        lines = plane["lines"]
        if interval_ns is not None:
            lo, hi = interval_ns
            lines = {k: [ev for ev in v if lo <= ev[1] < hi]
                     for k, v in lines.items()}
        busy_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not busy_line:
            continue
        merged = union([(s, s + d) for _, s, d in busy_line])
        devices.append({
            "name": plane["name"],
            "busy_s": sum(e - s for s, e in merged) / 1e9,
            "first_ns": merged[0][0], "last_ns": merged[-1][1],
            "merged": merged})
        for name, _, dur in lines.get(OPS_LINE, []):
            ops[op_key(name)] = ops.get(op_key(name), 0.0) + dur / 1e9
        for name, _, dur in lines.get(MODULES_LINE, []):
            m = modules.setdefault(module_key(name),
                                   {"calls": 0, "seconds": 0.0})
            m["calls"] += 1
            m["seconds"] += dur / 1e9
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0,
                "device_ops": [], "modules": {}, "idle_gaps": []}
    n = len(devices)
    first = min(d["first_ns"] for d in devices)
    last = max(d["last_ns"] for d in devices)
    if interval_ns is not None:
        first, last = interval_ns
    gaps = []
    merged = devices[0]["merged"]
    if interval_ns is not None:
        # The interval's own edges bound the idle before its first event and
        # after its last (an interval that opens on an arrival ends in the
        # schedule's lull: its longest gap).
        merged = [(first, first), *merged, (last, last)]
    for (_, end), (start, _) in zip(merged, merged[1:]):
        if start > end:
            gaps.append((start - end, end))
    gaps.sort(reverse=True)
    return {
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "window_s": (last - first) / 1e9,
        "per_device_busy_s": {d["name"]: d["busy_s"] for d in devices},
        "device_ops": [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "modules": {k: {"calls": v["calls"] / n, "seconds": v["seconds"] / n}
                    for k, v in modules.items()},
        "idle_gaps": [{"seconds": g / 1e9, "at_s": (at - first) / 1e9,
                       "at_ns": at} for g, at in gaps[:TOP]],
    }


def load_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_trace(path))
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.search(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)) for ev in line.events]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def main() -> None:
    src, dst = sys.argv[1:3]
    interval = None
    if len(sys.argv) >= 5:
        interval = (int(float(sys.argv[3]) * 1e9), int(float(sys.argv[4]) * 1e9))
    summary = reduce_planes(load_planes(src), interval)
    with open(dst, "w") as f:
        json.dump(summary, f)


if __name__ == "__main__":
    main()
