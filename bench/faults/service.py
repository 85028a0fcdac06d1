"""Faults the served path can have, each planted under a whole run.

Each fault takes a ``MonkeyPatch`` and breaks the program underneath the
service: a step that returns its state unchanged, half of a batch left
out, or an answer altered where it is produced.  ``bench/tests/
test_faults.py`` drives a tiny run with each and sees ``correct`` come out
false; ``bench/control.py --fault <name>`` reads one at the cell's own
size on the chip.
"""
import jax.numpy as jnp


def state_unchanged(mp):
    """``run_batch`` hands back its starting state."""
    from repro.core.driver import BatchRun, IterationDriver

    def run_batch(self, ops_batch, W0, *, T, carry=None, **kw):
        if carry is not None:
            return BatchRun(*carry[:3], extras=tuple(carry[3:]))
        kind, arr = self._stack_problems(ops_batch)
        Wb = jnp.broadcast_to(W0[:, None], (W0.shape[0], arr.shape[1])
                              + W0.shape[1:])
        return BatchRun(Wb, Wb, Wb)
    mp.setattr(IterationDriver, "run_batch", run_batch)


def half_batch(mp):
    """The first half of the batch is computed; the rest keep their
    starting state (a fresh start, or the carry they came with)."""
    from repro.core.driver import BatchRun, IterationDriver
    orig = IterationDriver.run_batch

    def run_batch(self, ops_batch, W0, *, T, carry=None, **kw):
        out = orig(self, ops_batch, W0, T=T, carry=carry, **kw)
        B = out.W.shape[0]
        keep = jnp.arange(B) < B // 2
        if carry is None:
            old = jnp.broadcast_to(W0[:, None], out.W.shape)
            olds = (old, old, old)
        else:
            olds = tuple(carry[:3])
        new = [jnp.where(keep.reshape((B,) + (1,) * (n.ndim - 1)), n, o)
               for n, o in zip(out.carries[:3], olds)]
        return BatchRun(*new, extras=out.extras)
    mp.setattr(IterationDriver, "run_batch", run_batch)


def answer_altered(mp):
    """Every answer's first row moved by 0.1 where ``run_batch`` makes it."""
    from repro.core.driver import IterationDriver
    orig = IterationDriver.run_batch

    def run_batch(self, *a, **kw):
        out = orig(self, *a, **kw)
        return out._replace(W=out.W.at[..., 0, :].add(0.1))
    mp.setattr(IterationDriver, "run_batch", run_batch)


FAULTS = [state_unchanged, half_batch, answer_altered]
