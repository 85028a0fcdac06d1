"""Host time per fleet tick blocked in ``fleet.stats``, in ms: the reads
of the drift statistics that the policy needs on the host after every
window pass (a device round trip each), summed over the window's ticks
and divided by them.  A program without the span reports nothing."""


def read(run):
    spans, ticks = run.counters.get("spans"), run.counters.get("ticks")
    if not spans or "fleet.stats" not in spans or not ticks:
        return None
    return 1e3 * spans["fleet.stats"][1] / ticks
