"""Tests of what PR 37 added beside the benchmark: the 36 per-layer metrics
that read the device thread's ledger, and ``readers/unqueued_share.py``. Not
tier-1; run with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import importlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark.lib import prom  # noqa: E402
from benchmark.readers import unqueued_share  # noqa: E402
from test_benchmark import _load  # noqa: E402

CELLS = {"chat": "gpt2m.chat", "decode": "olmoe.decode",
         "docqa": "qnext.docqa", "burstchat": "granite.burstchat"}
ALL = tuple(CELLS)
# (metric, mix) as PR 37 entered them, one entry a pair; since PR 42 one entry
# a metric whose ``workloads`` lists the cells, the mix left in the name only
# where one cell has the metric. ``join_behind_step_ms`` and
# ``join_turnaround_ms`` (eight of PR 37's thirty-six) are retired: 0.0 by
# construction since PR 38.
PAIRS = (
    *(("device_unqueued_live_share", t) for t in ALL),
    ("device_unqueued_empty_share.chat", "chat"),
    *(("device_idle_queued_share", t) for t in ALL),
    *(("step_starved_share", t) for t in ALL),
    *((f"join_{part}_ms", t) for part in ("dispatch", "run") for t in ALL),
    ("fetch_readback_ms.chat", "chat"),
    *((f"queue_wait_{part}_ms", t) for part in ("slot", "joins", "tick")
      for t in ("docqa", "burstchat")))
RETIRED = ("join_behind_step_ms", "join_turnaround_ms", "step_ms")
METRIC = "ai4e_decode_device_unqueued_seconds_total"


def test_thirty_six_entries_appended_after_the_seventy_the_benchmark_had():
    """Membership, not position: PR 37's twenty-eight surviving (metric,
    cell) pairs are ten entries since PR 42's fold, and the retired
    metrics have neither an entry nor a file."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(PAIRS) == 28 and len({name for name, _ in PAIRS}) == 10
    assert {name for name, _ in PAIRS} <= set(names)
    files = os.listdir(os.path.join(ROOT, "benchmark", "layer_metrics"))
    for gone in RETIRED:
        assert not [n for n in names if n.split(".")[0] == gone]
        assert not [f for f in files if f.split(".")[0] == gone]


@pytest.mark.parametrize("name,mix", PAIRS,
                         ids=[f"{n.split('.')[0]}.{t}" for n, t in PAIRS])
def test_new_metric_is_declared_and_silent_on_the_parents_program(name, mix):
    """Its definition file, a reader that imports, the accepted cell on its
    list, an end-to-end metric that every listed cell reports — and nothing
    from a program that has none of the ledger's series (the one ratio of two
    counters reads 0 there: the parent launched steps, and counted none as
    starved)."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert CELLS[mix] in entry["workloads"]
    assert set(entry["workloads"]) <= {w["name"]
                                       for w in manifest["workloads"]}
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert (entry["source"], entry["better"]) == ("program_counter", "lower")
    assert entry["layer"] in ("decode engine", "decode programs")
    definition = dict(_load(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")), name=name)
    reader = importlib.import_module(
        "benchmark.readers." + definition["reader"])
    old = prom.parse(
        'ai4e_decode_step_seconds_sum{phase="prefill"} 1.0\n'
        'ai4e_decode_step_seconds_count{phase="prefill"} 20\n'
        'ai4e_decode_queue_wait_seconds_sum{model="lm"} 1.0\n'
        'ai4e_decode_queue_wait_seconds_count{model="lm"} 20\n'
        'ai4e_decode_step_launches_total{kind="all",model="lm"} 900\n'
        'ai4e_decode_step_launches_total{kind="ahead",model="lm"} 890\n')
    ctx = {"prom_before": {}, "prom_after": old, "trace_prom_before": {},
           "trace_prom_after": old, "config": {"derived": {}}, "notes": {},
           "trace": {"window_s": 4.0, "busy_s": 3.0}}
    value = reader.read(definition, ctx)
    assert value == (0.0 if name.startswith("step_starved_share") else None)


def _scrape(**booked) -> dict:
    return prom.parse("\n".join(
        f'{METRIC}{{cause="{cause}",model="lm"}} {seconds}'
        for cause, seconds in booked.items()))


def _ctx(before, after, window=4.0, busy=2.8):
    return {"trace_prom_before": before, "trace_prom_after": after,
            "trace": {"window_s": window, "busy_s": busy}}


BEFORE = _scrape(empty=10.0, join=1.0, loop=0.5)
AFTER = _scrape(empty=10.8, join=1.12, loop=0.52)    # 0.8 + 0.12 + 0.02


@pytest.mark.parametrize("definition,expect", [
    ({"mode": "booked", "causes": ["join", "loop"]}, 100 * 0.14 / 4.0),
    ({"mode": "booked", "causes": ["empty"]}, 100 * 0.8 / 4.0),
    # 1.2 s idle in the trace, 0.94 s of it booked: 0.26 s with work queued.
    ({"mode": "idle_queued"}, 100 * (4.0 - 2.8 - 0.94) / 4.0)],
    ids=["live", "empty", "idle_queued"])
def test_unqueued_share_arithmetic(definition, expect):
    definition = dict(definition, metric=METRIC)
    assert unqueued_share.read(definition, _ctx(BEFORE, AFTER)) == \
        pytest.approx(expect)


def test_the_three_shares_add_up_to_the_traced_idle_share():
    ctx = _ctx(BEFORE, AFTER)
    shares = [unqueued_share.read(dict(d, metric=METRIC), ctx) for d in (
        {"mode": "booked", "causes": ["join", "loop"]},
        {"mode": "booked", "causes": ["empty"]}, {"mode": "idle_queued"})]
    assert sum(shares) == pytest.approx(100 * (1 - 2.8 / 4.0))


def test_a_cause_never_booked_counts_nothing_and_more_than_idle_is_negative():
    # No ``empty`` or ``loop`` series yet: the engine never idled.
    ctx = _ctx(_scrape(join=1.0), _scrape(join=1.3), busy=3.8)
    live = {"metric": METRIC, "mode": "booked", "causes": ["join", "loop"]}
    assert unqueued_share.read(live, ctx) == pytest.approx(7.5)
    assert unqueued_share.read(dict(live, causes=["empty"]), ctx) == 0.0
    # 0.2 s idle, 0.3 s booked: the ledger says more than the device had.
    assert unqueued_share.read({"metric": METRIC, "mode": "idle_queued"},
                               ctx) == pytest.approx(-2.5)


@pytest.mark.parametrize("mode", ["booked", "idle_queued"])
def test_unqueued_share_says_nothing_without_series_trace_or_window(mode):
    definition = {"metric": METRIC, "mode": mode, "causes": ["join"]}
    other = prom.parse('ai4e_decode_tokens_total{model="lm"} 5\n')
    assert unqueued_share.read(definition, _ctx(other, other)) is None
    assert unqueued_share.read(definition, _ctx({}, {})) is None
    ctx = _ctx(BEFORE, AFTER)
    assert unqueued_share.read(definition, dict(ctx, trace=None)) is None
    assert unqueued_share.read(definition, {
        "trace_prom_before": BEFORE, "trace_prom_after": AFTER}) is None
    for window in (0.0, -1.0):
        assert unqueued_share.read(
            definition, _ctx(BEFORE, AFTER, window=window)) is None
    with pytest.raises(ValueError):
        unqueued_share.read(dict(definition, mode="other"), ctx)
