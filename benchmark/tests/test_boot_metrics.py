"""Tests of what PR 53 added beside the benchmark: the ``prom_value`` reader
and the six per-layer metrics under ``setup_s`` that read the worker's boot
ledger (``ai4e_tpu/observability/boot.py``). Not tier-1; run with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import prom  # noqa: E402
from benchmark.readers import prom_value  # noqa: E402

BOOT_METRICS = ("boot_serving_s", "boot_reach_chip_s", "boot_build_s",
                "boot_warm_s", "boot_lower_s", "boot_compile_s")

SCRAPE = prom.parse("""\
# HELP ai4e_boot_seconds Wall seconds of this worker's start, by phase
# TYPE ai4e_boot_seconds gauge
ai4e_boot_seconds{phase="import"} 9.5
ai4e_boot_seconds{phase="backend"} 4.0
ai4e_boot_seconds{phase="build"} 15.0
ai4e_boot_seconds{phase="pools"} 2.5
ai4e_boot_seconds{phase="batch_warmup"} 0.25
ai4e_boot_seconds{phase="warm"} 27.0
ai4e_boot_seconds{phase="serve"} 1.0
ai4e_boot_seconds{phase="total"} 59.25
ai4e_jax_compile_seconds_total{stage="trace",when="boot"} 6.0
ai4e_jax_compile_seconds_total{stage="lower",when="boot"} 11.0
ai4e_jax_compile_seconds_total{stage="backend",when="boot"} 0.5
ai4e_jax_compile_seconds_total{stage="retrieve",when="boot"} 3.0
ai4e_jax_compile_seconds_total{stage="trace",when="serving"} 100.0
""")


def _definition(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(definition: dict, scrape):
    return prom_value.read(definition, {"prom_after": scrape})


def test_prom_value_sums_the_series_that_match_the_labels():
    every = {"metric": "ai4e_jax_compile_seconds_total",
             "labels": {"when": "boot"}}
    assert _read(every, SCRAPE) == 20.5   # serving's series left out
    assert _read(dict(every, scale=1000.0), SCRAPE) == 20500.0
    assert _read({"metric": "ai4e_boot_seconds",
                  "labels": {"phase": "total"}}, SCRAPE) == 59.25


def test_prom_value_adds_one_series_a_value_of_a_label():
    stages = {"metric": "ai4e_jax_compile_seconds_total",
              "labels": {"when": "boot"}, "label": "stage",
              "values": ["trace", "lower"]}
    assert _read(stages, SCRAPE) == 17.0


@pytest.mark.parametrize("scrape", [
    {}, None, prom.parse('ai4e_boot_seconds{phase="import"} 9.5\n')],
    ids=["a-parent-without-the-series", "no-scrape", "one-value-missing"])
def test_prom_value_says_nothing_where_a_series_is_absent(scrape):
    assert _read(_definition("boot_reach_chip_s"), scrape) is None
    assert _read({"metric": "ai4e_boot_seconds",
                  "labels": {"phase": "total"}}, scrape) is None


@pytest.mark.parametrize("name, value", [
    ("boot_serving_s", 59.25), ("boot_reach_chip_s", 13.5),
    ("boot_build_s", 17.5), ("boot_warm_s", 27.25), ("boot_lower_s", 17.0),
    ("boot_compile_s", 3.5)])
def test_each_definition_names_the_reader_and_reads_the_canned_scrape(
        name, value):
    definition = _definition(name)
    assert definition["reader"] == "prom_value" and definition["what"]
    assert _read(definition, SCRAPE) == value


def test_the_six_entries_move_setup_s_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-6:]] == list(
        BOOT_METRICS)
    layers = {m["layer"] for m in manifest["per_layer"][:-6]}
    for name in BOOT_METRICS:
        entry = entries[name]
        assert entry["moves"] == "setup_s" and entry["unit"] == "s"
        assert entry["better"] == "lower" and entry["workloads"] == cells
        assert entry["layer"] in layers   # a layer the manifest names
    # The parts of a boot add up to it: reach the chip, build, warm, serve.
    parts = sum(_read(_definition(n), SCRAPE) for n in (
        "boot_reach_chip_s", "boot_build_s", "boot_warm_s"))
    assert parts + 1.0 == _read(_definition("boot_serving_s"), SCRAPE)
