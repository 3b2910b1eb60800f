"""``trace_roofline`` for a decode step whose least bytes depend on how many
slots were live: a family with per-slot state that does not grow (a recurrent
state) has to move it for the LIVE slots only.

The live slots a step are the mean of ``ai4e_decode_step_active_slots`` over
the window (its histogram's sum / count, scraped at both edges); they reach
the family's ``ops_and_bytes`` as ``config["derived"]["live_slots"]``. Where
the program has no such series the reader says nothing. Parameters: those of
``trace_roofline``.
"""

from benchmark.lib import prom
from benchmark.readers import trace_roofline

SERIES = "ai4e_decode_step_active_slots"


def read(definition: dict, ctx: dict):
    steps = prom.delta(ctx["prom_before"], ctx["prom_after"],
                       SERIES + "_count")
    if steps <= 0:
        return None
    live = prom.delta(ctx["prom_before"], ctx["prom_after"],
                      SERIES + "_sum") / steps
    config = dict(ctx["config"],
                  derived=dict(ctx["config"]["derived"], live_slots=live))
    return trace_roofline.read(definition, dict(ctx, config=config))
