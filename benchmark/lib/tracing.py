"""Where in the window the traced seconds lie (``--trace 1``).

``run.py`` writes ``<work>/trace.start`` at ``trace_instant`` seconds of the
window; ``worker_launcher.py`` then starts the profiler and keeps
``TRACE_SECONDS`` of it, the kept interval opening once the profiler runs and
the worker's ``/metrics`` has been scraped — ``TRACE_LEAD_S`` allows for that.

The rule needs only what the run already knows, the window's length and the
instants its arrivals are due: the interval opens on the due instant nearest
to where the window's middle ``TRACE_SECONDS`` would open. A window placed
blindly falls, on some rotations of a sparse schedule, into the schedule's
longest lull, and a trace with no device operation in it has no window
(``lib/xplane.py`` reads ``window_s`` 0.0; PR 41 was refused on such a run of
``dots3.longdoc``). Anchored on an arrival, every rotation traces that
request's whole prefill and its first steps. A generator that knows no due
instants (``closed_loop``) hands none over and keeps the window's middle.
"""

from __future__ import annotations

TRACE_SECONDS = 4.0   # of the steady window, under --trace 1
# ``trace.start`` is written this long before the arrival the interval opens
# on. Between the file and the launcher's ``interval_epoch[0]`` lie its 50 ms
# poll, ``jax.profiler.start_trace`` and one scrape of ``/metrics``:
# 0.13-0.18 s in every traced run of PR 42 on the chip, whatever the cell
# (``notes.trace_placement.lead_measured_s``); the rest is margin, so the
# arrival's prefill BEGINS inside the interval.
TRACE_LEAD_S = 0.5


def middle_instant(seconds: float) -> float:
    """Where ``trace.start`` was written before PR 42, and still is without
    due instants: the traced seconds in the window's middle."""
    return max(0.0, (seconds - TRACE_SECONDS) / 2)


def trace_instant(seconds: float, dues=None) -> float:
    """Seconds into the window at which ``trace.start`` is written. ``dues``:
    the instants (seconds from the window's opening) at which the window's
    arrivals are due. The interval then opens ``TRACE_LEAD_S`` later, on the
    due instant nearest to the middle placement's own opening, among those
    that leave the lead before them and ``TRACE_SECONDS`` after them inside
    the window; with none of those, the middle placement."""
    middle = middle_instant(seconds)
    fits = [d for d in dues or ()
            if d >= TRACE_LEAD_S and d + TRACE_SECONDS <= seconds]
    if not fits:
        return middle
    return min(fits, key=lambda d: abs(d - (middle + TRACE_LEAD_S))) \
        - TRACE_LEAD_S
