"""The reference helper: a child process pinned to the host CPU.

Started when the run starts, it loads ``benchmark/references/<family>.py``,
calls ``prepare(config, pre)`` while the worker warms up (parameter values,
compilation, whatever can be computed before the window), writes
``<work>/ref_ready`` — the generator offers nothing before that file is
there, so the helper never computes beside the ramp or the window — then
sleeps until ``<work>/ref_jobs.json`` — the seeded sample of served outputs — and writes
``check(state, jobs)`` to ``<work>/ref_verdict.json``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    family, config_path, work = sys.argv[1:4]
    sys.path.insert(0, ROOT)
    with open(config_path) as f:
        config = json.load(f)
    with open(os.path.join(work, "ref_pre.json")) as f:
        pre = json.load(f)
    module = importlib.import_module(
        "benchmark.references." + family.replace("-", "_"))
    t0 = time.monotonic()
    state = module.prepare(config, pre)
    print(f"reference prepared in {time.monotonic() - t0:.1f}s", flush=True)
    open(os.path.join(work, "ref_ready"), "w").close()
    jobs_path = os.path.join(work, "ref_jobs.json")
    while not os.path.exists(jobs_path):
        time.sleep(0.1)
    with open(jobs_path) as f:
        jobs = json.load(f)
    t0 = time.monotonic()
    verdict = module.check(state, jobs)
    verdict["check_s"] = time.monotonic() - t0
    tmp = os.path.join(work, "ref_verdict.json.tmp")
    with open(tmp, "w") as f:
        json.dump(verdict, f)
    os.replace(tmp, os.path.join(work, "ref_verdict.json"))


if __name__ == "__main__":
    main()
