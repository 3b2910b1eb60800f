"""Device seconds of an XLA module a call, from the device trace.

The traced seconds of the modules whose name matches ``module`` (per device)
over their calls, times ``scale``. Where the trace holds no such module the
reader says nothing. Parameters: ``module`` (regular expression), ``scale``.
"""

import re


def read(definition: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("modules"):
        return None
    pattern = re.compile(definition["module"])
    matched = [m for name, m in trace["modules"].items()
               if pattern.search(name)]
    calls = sum(m["calls"] for m in matched)
    if not calls:
        return None
    return (sum(m["seconds"] for m in matched) / calls
            * definition.get("scale", 1.0))
