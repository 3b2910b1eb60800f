"""Tests of what PR 57 added beside the benchmark: two per-layer metrics
under ``setup_s`` that read, through the ``prom_value`` reader that is there,
how the worker's boot came by its decode programs
(``ai4e_tpu/observability/boot.py`` ``obtained``; the store is
``ai4e_tpu/runtime/executables.py``). Not tier-1; run with the others:

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

# A worker's ``/metrics`` after a start that loaded fourteen programs.
LOADED = prom.parse("""\
# HELP ai4e_boot_programs_total Programs this worker's start ran before serving
# TYPE ai4e_boot_programs_total counter
ai4e_boot_programs_total{outcome="loaded"} 14.0
ai4e_boot_programs_total{outcome="built"} 0.0
# TYPE ai4e_boot_program_load_seconds_total counter
ai4e_boot_program_load_seconds_total 5.25
ai4e_boot_seconds{phase="warm"} 7.5
""")
# ... and after a first start, on an empty store.
BUILT = prom.parse("""\
ai4e_boot_programs_total{outcome="loaded"} 0.0
ai4e_boot_programs_total{outcome="built"} 14.0
ai4e_boot_program_load_seconds_total 0.0
""")
# The parent commit's worker has neither series.
PARENT = prom.parse('ai4e_boot_seconds{phase="warm"} 27.0\n')


def _definition(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, scrape, value", [
    ("boot_loaded_programs", LOADED, 14.0), ("boot_load_s", LOADED, 5.25),
    ("boot_loaded_programs", BUILT, 0.0), ("boot_load_s", BUILT, 0.0),
    ("boot_loaded_programs", PARENT, None), ("boot_load_s", PARENT, None),
    ("boot_loaded_programs", None, None), ("boot_load_s", {}, None)])
def test_each_definition_reads_the_recorded_scrape(name, scrape, value):
    definition = _definition(name)
    assert definition["reader"] == "prom_value" and definition["what"]
    assert prom_value.read(definition, {"prom_after": scrape}) == value


def test_the_two_entries_move_setup_s_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit, better in (("boot_loaded_programs", "programs", "higher"),
                               ("boot_load_s", "s", "lower")):
        entry = entries[name]
        assert (entry["unit"], entry["better"]) == (unit, better)
        assert entry["moves"] == "setup_s" and entry["workloads"] == cells
        assert entry["source"] == "program_counter"
        # The layer of the boot's other metrics of the decode programs.
        assert entry["layer"] == entries["boot_warm_s"]["layer"]
