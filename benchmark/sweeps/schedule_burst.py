#!/usr/bin/env python3
"""``schedule_cell.py`` for a cell whose mix arrives in bursts
(``generators/burst_loop.py``): the same queue model and the same judgement,
replaying the burst schedule.

    python3 benchmark/sweeps/schedule_burst.py <workload> --tick S --join S \\
        --join-token S [--set KEY=JSON ...] [--scan FIRST LAST | seed ...]

``schedule_model.p95s`` and ``schedule_cell.cap_at_rank`` look the schedule
up by name in their own modules: both names are pointed at
``burst_loop.schedule`` here, once, and ``schedule_cell.main`` does the rest.
``granite.burstchat`` (PR 34): the constants are in
``sweeps/granite.burstchat.md``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import schedule_cell  # noqa: E402
import schedule_model  # noqa: E402
from benchmark.generators import burst_loop  # noqa: E402

schedule_model.schedule = schedule_cell.schedule = burst_loop.schedule

if __name__ == "__main__":
    schedule_cell.main()
