"""The program's own marks in a JAX profiler trace (``*.xplane.pb``): the
``ai4e.*`` regions the program opens with ``device_trace`` (host planes;
recorded only with the profiler's host tracer at level 1 or more), device
seconds by ``jax.named_scope``, and the device's idle gaps named by the
program's region that covers them — all on the profiler's one clock.

    python benchmark/lib/xplane_spans.py <trace dir or file> <out.json> \
        [<from_s> <to_s>]

The file is read as protobuf wire format (XSpace → XPlane → XLine → XEvent):
``jax.profiler.ProfileData`` shows an event's own stats but not its
metadata's, and the compiler keeps each operation's scope path
(``tf_op``: ``jit(step)/SeqFormerLM.decode_step/block0.step/cache_update/mul``)
and its program (``program_id``) there. No JAX, no TensorFlow.

Not yet called by ``run.py`` (PERF.md 7d says which three edits wire it in);
run it on a trace kept with ``run.py --keep-trace``.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))   # run as a script, like xplane.py

from benchmark.lib.xplane import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, TOP, find_trace, module_key, op_key,
    union)

SPAN_PREFIX = "ai4e."
HOST_PLANE = "/host:CPU"
# The scopes the decode programs declare (models/seqformer.py, kvcache.py).
DECODE_SCOPES = ("cache_update", "attention", "mlp", "embedding", "head",
                 "cache_insert")
UNSCOPED, ELSEWHERE, UNATTRIBUTED = "unscoped", "elsewhere", "unattributed"


# -- protobuf wire format ------------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes):
    """``(field number, value)`` of one message: varints as ints, 64-bit as
    raw 8 bytes, length-delimited as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf: bytes, stat_names: dict) -> tuple[str, object]:
    """One XStat → ``(name, value)``."""
    name, value = "", None
    for number, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = v.decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def read_planes(path: str) -> list[dict]:
    """``[{"name", "lines": {line name: [event]}}]`` with each event
    ``{"name", "start_ns", "dur_ns", "stats", "meta"}`` — ``stats`` the
    event's own, ``meta`` its metadata's (``tf_op``, ``program_id``, ...).
    Only device planes and the host plane are decoded."""
    with open(find_trace(path), "rb") as f:
        space = f.read()
    planes = []
    for number, plane_buf in _fields(space):
        if number != 1:
            continue
        parts = list(_fields(plane_buf))
        name = next((v.decode() for n, v in parts if n == 2), "")
        if not (DEVICE_PLANE.search(name) or name == HOST_PLANE):
            continue
        stat_names = {}
        for n, v in parts:
            if n == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (x.decode() for k, x in _fields(meta) if k == 2), "")
        events_meta = {}
        for n, v in parts:
            if n == 4:
                key, meta = _map_entry(v)
                entry = {"name": "", "stats": {}}
                for k, x in _fields(meta):
                    if k == 2:
                        entry["name"] = x.decode("utf-8", "replace")
                    elif k == 5:
                        stat, value = _stat(x, stat_names)
                        entry["stats"][stat] = value
                events_meta[key] = entry
        lines: dict = {}
        for n, v in parts:
            if n != 3:
                continue
            line_name, t0_ns, raw_events = "", 0, []
            for k, x in _fields(v):
                if k == 2:
                    line_name = x.decode()
                elif k == 3:
                    t0_ns = _signed(x)
                elif k == 4:
                    raw_events.append(x)
            events = lines.setdefault(line_name, [])
            for raw in raw_events:
                meta_id = offset_ps = dur_ps = 0
                stats = {}
                for k, x in _fields(raw):
                    if k == 1:
                        meta_id = x
                    elif k == 2:
                        offset_ps = _signed(x)
                    elif k == 3:
                        dur_ps = _signed(x)
                    elif k == 4:
                        stat, value = _stat(x, stat_names)
                        stats[stat] = value
                meta = events_meta.get(meta_id, {"name": "", "stats": {}})
                events.append({"name": meta["name"],
                               "start_ns": t0_ns + offset_ps // 1000,
                               "dur_ns": dur_ps // 1000,
                               "stats": stats, "meta": meta["stats"]})
        planes.append({"name": name, "lines": lines})
    return planes


# -- arithmetic (tested on hand-made planes) ------------------------------------

def host_spans(planes: list[dict]) -> list[dict]:
    """The ``ai4e.*`` regions of the host plane, by start:
    ``{"name", "thread", "start_ns", "dur_ns", "stats"}``. ``thread`` is
    the line's index on the plane (the profiler names every Python thread's
    line alike)."""
    out = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for thread, events in enumerate(plane["lines"].values()):
            out += [{"name": ev["name"], "thread": thread,
                     "start_ns": ev["start_ns"], "dur_ns": ev["dur_ns"],
                     "stats": ev["stats"]}
                    for ev in events if ev["name"].startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda s: s["start_ns"])


def scope_of(tf_op: str | None, scopes=DECODE_SCOPES) -> str:
    """The innermost declared scope on an operation's path; ``unscoped``
    for an operation the compiler made (no path), ``elsewhere`` for one
    under no declared scope (the Flax submodules between them)."""
    if not tf_op:
        return UNSCOPED
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in scopes:
            return part
    return ELSEWHERE


def scope_seconds(planes: list[dict], interval_ns=None,
                  scopes=DECODE_SCOPES) -> dict:
    """Device seconds of ``XLA Ops`` by program and scope, averaged over
    the device planes that ran anything:
    ``{program: {"seconds", "by_scope": {scope: seconds}, "unscoped_ops":
    [[operation, seconds]]}}`` — the last names what the remainder is, so
    that it is reported and not hidden. An operation belongs to the program
    whose ``program_id`` its metadata carries."""
    programs: dict = {}
    devices = 0
    for plane in planes:
        if not DEVICE_PLANE.search(plane["name"]):
            continue
        ops = plane["lines"].get(OPS_LINE, [])
        if interval_ns is not None:
            lo, hi = interval_ns
            ops = [ev for ev in ops if lo <= ev["start_ns"] < hi]
        if not ops:
            continue
        devices += 1
        names = {}
        for ev in plane["lines"].get(MODULES_LINE, []):
            m = re.search(r"\((\d+)\)$", ev["name"])
            if m:
                names[int(m.group(1))] = module_key(ev["name"])
        for ev in ops:
            program = names.get(ev["meta"].get("program_id"), "?")
            entry = programs.setdefault(program, {
                "seconds": 0.0, "by_scope": {}, "unscoped_ops": {}})
            scope = scope_of(ev["meta"].get("tf_op"), scopes)
            seconds = ev["dur_ns"] / 1e9
            entry["seconds"] += seconds
            entry["by_scope"][scope] = (entry["by_scope"].get(scope, 0.0)
                                        + seconds)
            if scope == UNSCOPED:
                name = op_key(ev["name"])
                entry["unscoped_ops"][name] = (
                    entry["unscoped_ops"].get(name, 0.0) + seconds)
    for entry in programs.values():
        entry["seconds"] /= devices
        entry["by_scope"] = {k: v / devices
                             for k, v in entry["by_scope"].items()}
        entry["unscoped_ops"] = [[k, v / devices] for k, v in sorted(
            entry["unscoped_ops"].items(), key=lambda kv: -kv[1])[:TOP]]
    return programs


def cover(gap: tuple[int, int], spans: list[dict], starts: list[int],
          longest_ns: int) -> dict:
    """Nanoseconds of ``gap`` by the innermost span covering each instant
    (spans nest: a tick holds its prepare and its bookkeeping), the rest
    under ``unattributed``. ``spans`` sorted by start, ``starts`` their
    starts, ``longest_ns`` the longest span's duration."""
    lo, hi = gap
    first = bisect.bisect_left(starts, lo - longest_ns)
    last = bisect.bisect_right(starts, hi)
    over = [(max(s["start_ns"], lo), min(s["start_ns"] + s["dur_ns"], hi), s)
            for s in spans[first:last]
            if s["start_ns"] < hi and s["start_ns"] + s["dur_ns"] > lo]
    edges = sorted({lo, hi, *(a for a, _, _ in over),
                    *(b for _, b, _ in over)})
    out: dict = {}
    for a, b in zip(edges, edges[1:]):
        inside = [s for x, y, s in over if x <= a and y >= b]
        name = (min(inside, key=lambda s: s["dur_ns"])["name"] if inside
                else UNATTRIBUTED)
        out[name] = out.get(name, 0) + (b - a)
    return out


def gap_label(at_s: float, shares: list[list]) -> str:
    """``+2.647s in ai4e.decode.bookkeeping 62% / ai4e.decode.dispatch
    21%``; ``+2.647s unattributed`` where no span overlaps."""
    named = [f"{name} {100 * share:.0f}%" for name, share in shares
             if name != UNATTRIBUTED and share >= 0.005]
    return f"+{at_s:.3f}s " + ("in " + " / ".join(named) if named
                               else UNATTRIBUTED)


def attribute_gaps(planes: list[dict], spans: list[dict],
                   interval_ns=None) -> dict:
    """The first device plane's idle gaps (between the merged ``XLA Ops``
    intervals, as ``xplane.reduce_planes`` takes them) against the host
    spans: idle seconds in all, by covering span, and the longest gaps each
    with its spans' shares and its label."""
    ops = next((p["lines"].get(OPS_LINE, []) for p in planes
                if DEVICE_PLANE.search(p["name"])
                and p["lines"].get(OPS_LINE)), [])
    if interval_ns is not None:
        lo, hi = interval_ns
        ops = [ev for ev in ops if lo <= ev["start_ns"] < hi]
    merged = union([(ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
                    for ev in ops])
    if not merged:
        return {"idle_s": 0.0, "attributed_s": 0.0, "by_span": {},
                "idle_gaps": []}
    origin = interval_ns[0] if interval_ns is not None else merged[0][0]
    starts = [s["start_ns"] for s in spans]
    longest = max((s["dur_ns"] for s in spans), default=0)
    by_span: dict = {}
    gaps = []
    for (_, end), (start, _) in zip(merged, merged[1:]):
        if start <= end:
            continue
        covered = cover((end, start), spans, starts, longest)
        for name, ns in covered.items():
            by_span[name] = by_span.get(name, 0) + ns
        gaps.append((start - end, end, covered))
    idle = sum(g for g, _, _ in gaps)
    gaps.sort(key=lambda g: -g[0])
    top = []
    for ns, at, covered in gaps[:TOP]:
        shares = sorted(([name, part / ns] for name, part in covered.items()),
                        key=lambda kv: -kv[1])
        at_s = (at - origin) / 1e9
        top.append({"seconds": ns / 1e9, "at_s": at_s, "at_ns": at,
                    "spans": shares, "label": gap_label(at_s, shares)})
    return {"idle_s": idle / 1e9,
            "attributed_s": (idle - by_span.get(UNATTRIBUTED, 0)) / 1e9,
            "by_span": {k: v / 1e9 for k, v in sorted(
                by_span.items(), key=lambda kv: -kv[1])},
            "idle_gaps": top}


def summarize(planes: list[dict], interval_ns=None, module: str = "^jit_step$",
              scopes=DECODE_SCOPES) -> dict:
    spans = host_spans(planes)
    if interval_ns is not None:
        lo, hi = interval_ns
        in_window = [s for s in spans if lo <= s["start_ns"] < hi]
    else:
        in_window = spans
    by_name: dict = {}
    for s in in_window:
        entry = by_name.setdefault(s["name"], {"count": 0, "seconds": 0.0})
        entry["count"] += 1
        entry["seconds"] += s["dur_ns"] / 1e9
    programs = scope_seconds(planes, interval_ns, scopes)
    gaps = attribute_gaps(planes, spans, interval_ns)
    out = {"host_spans": by_name, "scopes": programs, **gaps}
    pattern = re.compile(module)
    chosen = [p for name, p in programs.items() if pattern.search(name)]
    total = sum(p["seconds"] for p in chosen)
    if total > 0:
        out["scope_share"] = {
            scope: 100.0 * sum(p["by_scope"].get(scope, 0.0)
                               for p in chosen) / total
            for scope in (*scopes, UNSCOPED, ELSEWHERE)}
    if gaps["idle_s"] > 0:
        out["gap_attributed_share"] = (100.0 * gaps["attributed_s"]
                                       / gaps["idle_s"])
    return out


def main() -> None:
    src, dst = sys.argv[1:3]
    interval = None
    if len(sys.argv) >= 5:
        interval = (int(float(sys.argv[3]) * 1e9), int(float(sys.argv[4]) * 1e9))
    summary = summarize(read_planes(src), interval)
    with open(dst, "w") as f:
        json.dump(summary, f, indent=1)
    for gap in summary["idle_gaps"]:
        print(f"{gap['seconds'] * 1e3:8.3f} ms  {gap['label']}")
    print(json.dumps({k: summary.get(k) for k in (
        "gap_attributed_share", "scope_share", "idle_s", "by_span")}))
    print("jit_step unscoped:", json.dumps(
        summary["scopes"].get("jit_step", {}).get("unscoped_ops")))


if __name__ == "__main__":
    main()
