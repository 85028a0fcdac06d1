"""Share of its roofline that the tracking + FastMix kernel reaches in the
served solves: the least time of the logical work of every iteration the
window served (``work.fastmix_track`` at the request's m, d, k, K, once
per iteration), over the summed device time of the kernel in the trace.
The kernel body is ``_fastmix_track_kernel``; its trace events carry the
name of the jitted wrapper around its ``pallas_call``,
``_fastmix_track_fused``."""
import work

KERNEL = "_fastmix_track_fused"


def read(run):
    if run.trace is None or not run.counters.get("iterations"):
        return None
    s = run.counters["shape"]
    calls = run.counters["iterations"]
    return work.roofline_share(
        calls * work.fastmix_track(s["m"], s["d"], s["k"], s["K"]),
        run.trace.kernel_s(KERNEL), run.peaks)
