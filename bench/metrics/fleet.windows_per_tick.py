"""Batched window launches per fleet tick: the ``fleet.window`` spans the
window's tracer counted, over the ticks the window ran.  One base window
per bucket is the floor; restart and escalation re-runs of whole buckets
are what rises above it.  A program without the span reports nothing."""


def read(run):
    spans, ticks = run.counters.get("spans"), run.counters.get("ticks")
    if not spans or "fleet.window" not in spans or not ticks:
        return None
    return spans["fleet.window"][0] / ticks
