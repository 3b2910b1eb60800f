"""Tests of what PR 24 added beside the benchmark: the two new readers, the
ten new per-layer metric definitions, and ``lib/xplane_spans.py`` (host
spans, scopes and gap attribution). Not tier-1; run with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark.lib import prom, xplane, xplane_spans  # noqa: E402
from benchmark.readers import counter_ratio, prom_mean_sum  # noqa: E402
from test_benchmark import _load, _run, cpu_root  # noqa: E402,F401 — cpu_root is a fixture

# One entry a metric since PR 42: the names PR 24 gave carried the mix.
NEW_METRICS = (
    "tick_device_wait_ms", "tick_host_ms", "tick_admit_ms", "queue_wait_ms",
    "step_active_slots", "kv_useful_share", "shell_in_ms", "shell_out_ms",
    "fabric_queue_ms", "fabric_deliver_ms")


def _tick_scrape(ticks: int, seconds: dict) -> dict:
    lines = []
    for phase, total in seconds.items():
        lines.append(f'ai4e_decode_tick_seconds_sum{{model="lm",'
                     f'phase="{phase}"}} {total}')
        lines.append(f'ai4e_decode_tick_seconds_count{{model="lm",'
                     f'phase="{phase}"}} {ticks}')
    return prom.parse("\n".join(lines))


HOST = ("prepare", "handoff", "dispatch", "return", "bookkeeping", "yield")


def test_prom_mean_sum_adds_the_means_or_says_nothing():
    definition = {"metric": "ai4e_decode_tick_seconds", "label": "phase",
                  "values": list(HOST), "scale": 1000.0}
    before = _tick_scrape(10, dict.fromkeys((*HOST, "admit"), 1.0))
    after = _tick_scrape(30, dict({p: 1.0 + 0.002 * (i + 1)
                                   for i, p in enumerate(HOST)}, admit=9.0))
    ctx = {"prom_before": before, "prom_after": after}
    # 20 ticks; means 0.1, 0.2, ... 0.6 ms; admit is not one of the values.
    assert prom_mean_sum.read(definition, ctx) == pytest.approx(2.1)
    # One phase that did not move: the sum is unknown, not smaller.
    partial = {k: v for k, v in after.items()
               if ("phase", "yield") not in k[1]}
    assert prom_mean_sum.read(definition, {"prom_before": before,
                                           "prom_after": partial}) is None
    # The parent's program has no such histogram at all.
    assert prom_mean_sum.read(definition, {"prom_before": {},
                                           "prom_after": {}}) is None


def test_counter_ratio():
    definition = {"metric": "ai4e_decode_kv_positions_total",
                  "numerator": {"kind": "live"},
                  "denominator": {"kind": "attended"}, "scale": 100.0}

    def scrape(live, attended):
        return prom.parse(
            f'ai4e_decode_kv_positions_total{{kind="live",model="lm"}} '
            f'{live}\nai4e_decode_kv_positions_total{{kind="attended",'
            f'model="lm"}} {attended}\n')
    ctx = {"prom_before": scrape(100, 1000), "prom_after": scrape(350, 2000)}
    assert counter_ratio.read(definition, ctx) == pytest.approx(25.0)
    assert counter_ratio.read(definition, {
        "prom_before": scrape(1, 5), "prom_after": scrape(1, 5)}) is None
    assert counter_ratio.read(definition, {"prom_before": {},
                                           "prom_after": {}}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_and_silent_on_the_parents_program(name):
    """Each new metric has its definition file, a reader that exists, an
    entry in ``BENCHMARK.json``'s ``per_layer`` that lists the chat cell —
    and on a program without the new series and stamps it returns nothing."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert "gpt2m.chat" in entry["workloads"]
    assert entry["moves"] in {m["name"] for m in manifest["end_to_end"]}
    definition = dict(_load(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")), name=name)
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    # What the parent's worker exposes: the old decode series and stamps.
    old = prom.parse('ai4e_decode_step_seconds_sum{phase="decode"} 1.0\n'
                     'ai4e_decode_step_seconds_count{phase="decode"} 20\n')
    ledger = [{"e": "admitted", "h": "gateway", "t": 1.0},
              {"e": "chunk", "h": "decode", "t": 1.2, "ms": 30.0}]
    ctx = {"prom_before": {}, "prom_after": old, "ledgers": [ledger],
           "config": {"derived": {}}, "gauge_samples": [], "notes": {}}
    assert reader.read(definition, ctx) is None


# -- lib/xplane_spans.py on hand-made planes ------------------------------------

def _op(name, start, dur, tf_op=None, program=7):
    meta = {"program_id": program}
    if tf_op:
        meta["tf_op"] = tf_op
    return {"name": name, "start_ns": start, "dur_ns": dur, "stats": {},
            "meta": meta}


def _span(name, start, dur, **stats):
    return {"name": name, "start_ns": start, "dur_ns": dur, "stats": stats,
            "meta": {}}


def _planes():
    step = "jit(step)/SeqFormerLM.decode_step/block0.step/"
    device = {"name": "/device:TPU:0", "lines": {
        "XLA Modules": [_op("jit_step(7)", 0, 500)],
        "XLA Ops": [
            _op("%fusion.1", 0, 100, step + "cache_update/mul:"),
            _op("%copy.48", 200, 100),                       # compiler-made
            _op("%fusion.2", 400, 60, step + "attention/dot_general:"),
            _op("%fusion.3", 460, 40, step + "qkv/dot_general:")]}}
    host = {"name": "/host:CPU", "lines": {
        "python3": [_span("ai4e.decode.tick", 80, 140, tick=3, active=2),
                    _span("ai4e.decode.bookkeeping", 100, 60),
                    _span("not.ours", 0, 1000)],
        "python3 ": [_span("ai4e.decode.dispatch", 160, 50)]}}
    return [device, host]


def test_gap_covered_60_40_and_a_gap_with_no_span():
    planes = _planes()
    spans = xplane_spans.host_spans(planes)
    assert [s["name"] for s in spans] == [
        "ai4e.decode.tick", "ai4e.decode.bookkeeping",
        "ai4e.decode.dispatch"]
    assert spans[0]["stats"] == {"tick": 3, "active": 2}
    assert spans[0]["thread"] != spans[2]["thread"]
    out = xplane_spans.attribute_gaps(planes, spans)
    # Gaps: [100, 200) under bookkeeping 60 then dispatch 40 (the tick
    # around both is never the innermost); [300, 400) under nothing.
    assert out["idle_s"] == pytest.approx(200e-9)
    assert out["attributed_s"] == pytest.approx(100e-9)
    assert out["by_span"] == pytest.approx({
        "unattributed": 100e-9, "ai4e.decode.bookkeeping": 60e-9,
        "ai4e.decode.dispatch": 40e-9})
    labels = {g["at_ns"]: g["label"] for g in out["idle_gaps"]}
    assert labels[100] == ("+0.000s in ai4e.decode.bookkeeping 60% / "
                           "ai4e.decode.dispatch 40%")
    assert labels[300] == "+0.000s unattributed"
    # The same gaps as the existing reduction takes them.
    old = xplane.reduce_planes([{
        "name": p["name"], "lines": {k: [(e["name"], e["start_ns"],
                                          e["dur_ns"]) for e in v]
                                     for k, v in p["lines"].items()}}
        for p in planes[:1]])
    assert sorted(g["at_ns"] for g in old["idle_gaps"]) == [100, 300]


def test_tick_alone_names_the_thread_hops():
    """Inside a tick but inside none of its children: the hops between the
    loop and the device thread."""
    spans = [_span("ai4e.decode.tick", 0, 100),
             _span("ai4e.decode.prepare", 0, 10)]
    covered = xplane_spans.cover((5, 30), spans, [0, 0], 100)
    assert covered == {"ai4e.decode.prepare": 5, "ai4e.decode.tick": 20}


def test_scopes_with_an_unscoped_op():
    summary = xplane_spans.summarize(_planes())
    step = summary["scopes"]["jit_step"]
    assert step["seconds"] == pytest.approx(300e-9)
    assert step["by_scope"] == pytest.approx({
        "cache_update": 100e-9, "unscoped": 100e-9, "attention": 60e-9,
        "elsewhere": 40e-9})
    assert step["unscoped_ops"] == [["%copy.48", pytest.approx(100e-9)]]
    assert summary["scope_share"]["cache_update"] == pytest.approx(100 / 3)
    assert summary["scope_share"]["unscoped"] == pytest.approx(100 / 3)
    assert summary["gap_attributed_share"] == pytest.approx(50.0)
    assert summary["host_spans"]["ai4e.decode.tick"] == {
        "count": 1, "seconds": pytest.approx(140e-9)}
    # Only what begins inside the interval.
    late = xplane_spans.summarize(_planes(), interval_ns=(150, 1000))
    assert "cache_update" not in late["scopes"]["jit_step"]["by_scope"]
    assert list(late["host_spans"]) == ["ai4e.decode.dispatch"]


def test_wire_reader_agrees_with_profile_data_on_the_recorded_trace():
    path = os.path.join(HERE, "data", "tiny.xplane.pb")
    mine = [p for p in xplane_spans.read_planes(path)
            if xplane.DEVICE_PLANE.search(p["name"])]
    theirs = xplane.load_planes(path)
    assert [p["name"] for p in mine] == [p["name"] for p in theirs]
    for a, b in zip(mine, theirs):
        for line in ("XLA Ops", "XLA Modules"):
            got = [(e["name"], e["start_ns"], e["dur_ns"])
                   for e in a["lines"][line]]
            assert len(got) == len(b["lines"][line]) > 0
            for (n1, s1, d1), (n2, s2, d2) in zip(got, b["lines"][line]):
                assert n1 == n2 and abs(s1 - s2) <= 1 and abs(d1 - d2) <= 1
    op = mine[0]["lines"]["XLA Ops"][0]
    assert "program_id" in op["meta"]


# -- the CPU rehearsal prints every new metric ----------------------------------

def test_rehearsal_reports_every_new_metric(cpu_root):  # noqa: F811
    manifest_path = os.path.join(cpu_root, "manifest.cpu.json")
    proc = _run(cpu_root, manifest_path, "gpt2m.chat", 1, seconds=6.0)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    reported = line["rehearsal_metrics"]
    assert set(NEW_METRICS) <= set(reported), sorted(reported)
    tick = (reported["tick_device_wait_ms"]["value"]
            + reported["tick_host_ms"]["value"]
            + reported["tick_admit_ms"]["value"])
    # The eight phases partition the step-to-step interval: their means
    # add up to the mean gap between a stream's tokens.
    assert tick == pytest.approx(reported["engine_itl_ms"]["value"],
                                 rel=0.15)
    assert 0 < reported["kv_useful_share"]["value"] <= 100
    assert 1 <= reported["step_active_slots"]["value"] <= 8
    assert "compile phases inside the window: 0" in proc.stdout
