"""The rehearsal tables of ``tests/test_benchmark.py`` (``CPU_CUT``,
``TRAFFIC_CUT``, ``REHEARSALS``) filled from data: every
``tests/data/cpu_cuts/<cell>.json`` names the CPU cut of its family
(``family``), of its mix (``traffic``) and the rehearsals its own test file
runs (``rehearsals``), so a new cell brings a file of numbers and no code —
a PR that is not a ``benchmark`` PR edits no file the benchmark has, and
``test_benchmark.py`` is one. It sits a directory above
``tests/conftest.py`` so that pytest loads it for any selection of
``benchmark/tests``: the ``cpu_root`` fixture cuts EVERY configuration of the
manifest, so each cut must be known whichever file is run."""

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "tests"))

import test_benchmark as tb  # noqa: E402

for path in sorted(glob.glob(os.path.join(HERE, "tests", "data", "cpu_cuts",
                                          "*.json"))):
    with open(path) as f:
        cuts = json.load(f)
    tb.CPU_CUT.update(cuts["family"])
    tb.TRAFFIC_CUT.update(cuts["traffic"])
    tb.REHEARSALS += [tuple(r) for r in cuts["rehearsals"]]
