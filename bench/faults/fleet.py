"""Faults the fleet's tracking path can have, each planted under a whole
run.

Each fault takes a ``MonkeyPatch`` and breaks the program underneath
``TrackerFleet``: a tick that hands back its carry unchanged, half of a
bucket's slots left where they were, an answer altered where it is
produced, or Alg. 2's sign reference pinned to the tenant's start ``W0``
(the fault the re-anchored reference cures).  ``bench/tests/
test_faults.py`` drives a tiny run with each and sees ``correct`` come
out false; ``bench/control.py --fault <name>`` reads one at the cell's
own size on the chip.
"""
import jax.numpy as jnp


def carry_unchanged(mp):
    """``run_batch`` hands back the carry it resumed from."""
    from repro.core.driver import BatchRun, IterationDriver
    orig = IterationDriver.run_batch

    def run_batch(self, ops_batch, W0, *, T, carry=None, **kw):
        if carry is None:
            return orig(self, ops_batch, W0, T=T, **kw)
        carry = self.step.normalize_carry(tuple(carry))
        return BatchRun(*carry[:3], extras=tuple(carry[3:]))
    mp.setattr(IterationDriver, "run_batch", run_batch)


def half_bucket(mp):
    """Every other slot of a bucket advances; the rest keep the carry they
    came with."""
    from repro.core.driver import BatchRun, IterationDriver
    orig = IterationDriver.run_batch

    def run_batch(self, ops_batch, W0, *, T, carry=None, **kw):
        out = orig(self, ops_batch, W0, T=T, carry=carry, **kw)
        if carry is None:
            return out
        B = out.W.shape[0]
        keep = jnp.arange(B) % 2 == 0
        new = [jnp.where(keep.reshape((B,) + (1,) * (n.ndim - 1)), n, o)
               for n, o in zip(out.carries, carry)]
        return BatchRun(*new[:3], extras=tuple(new[3:]), diag=out.diag)
    mp.setattr(IterationDriver, "run_batch", run_batch)


def answer_altered(mp):
    """Every estimate's first row moved by 0.1 where ``run_batch`` makes
    it."""
    from repro.core.driver import IterationDriver
    orig = IterationDriver.run_batch

    def run_batch(self, *a, **kw):
        out = orig(self, *a, **kw)
        return out._replace(W=out.W.at[..., 0, :].add(0.1))
    mp.setattr(IterationDriver, "run_batch", run_batch)


def reference_pinned(mp):
    """Every window's sign reference is the slot's tenant's start ``W0``
    for its whole life, as before the reference was re-anchored."""
    from repro.streaming import TrackerFleet
    join, window = TrackerFleet.join, TrackerFleet._window
    starts = {}

    def pinned_join(self, tid, W0, **kw):
        slot = join(self, tid, W0, **kw)
        t = self._tenants[tid]
        starts[t.bucket, slot] = jnp.asarray(W0)
        return slot

    def pinned_window(self, bkt, ops_b, carry, ref, *a, **kw):
        ref = jnp.stack([starts.get((bkt.key, s), ref[s])
                         for s in range(bkt.capacity)]).astype(ref.dtype)
        return window(self, bkt, ops_b, carry, ref, *a, **kw)
    mp.setattr(TrackerFleet, "join", pinned_join)
    mp.setattr(TrackerFleet, "_window", pinned_window)


FAULTS = [carry_unchanged, half_bucket, answer_altered, reference_pinned]
