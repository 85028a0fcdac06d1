"""Share of its roofline that the Gram kernel of CholeskyQR2 reaches in the
served solves: the least time of two ``(d, k)`` Grams per agent per
iteration served (``work.gram_per_iteration``), over the summed device
time of the kernel (``_gram_kernel``; its trace events carry its jitted
wrapper's name, ``jit__gram_``, under vmap ``vmap_jit__gram__``) in the
trace."""
import work

KERNEL = "__gram_"


def read(run):
    if run.trace is None or not run.counters.get("iterations"):
        return None
    s = run.counters["shape"]
    return work.roofline_share(
        run.counters["iterations"]
        * work.gram_per_iteration(s["m"], s["d"], s["k"]),
        run.trace.kernel_s(KERNEL), run.peaks)
