"""Jaxpr-level dtype-flow audits of the public entry points.

Abstract-evals (``jax.make_jaxpr``) the paper-facing surface — ``deepca``,
``depca``, ``IterationDriver.run``/``run_batch``, both consensus engines'
``mix``/``mix_track``/``apply_mix_track`` families — and walks the closed
jaxprs (recursing through pjit/scan/cond *and* ``pallas_call`` kernel
bodies) to verify two contracts:

* **f64 fidelity** (:func:`check_f64`): with f64 inputs, no equation may
  consume an f64 operand and produce a narrower float — the x64
  paper-fidelity runs chase <1e-8 targets and a single silent f32 hop
  (e.g. routing an f64 iterate through the fp32 Pallas kernel) caps the
  whole run at ~1e-6.
* **wire accumulation** (:func:`check_wire`): on every wire-precision
  path (``wire_dtype`` bf16 / int8 / fp8) the *only* consumers allowed
  to touch the wire dtype are the quantize/dequantize casts themselves;
  any equation that reads the wire dtype and writes a sub-fp32 float
  (accumulating in or near wire precision) breaks the noisy-power-method
  error bound the wire mode's license rests on.  The check also requires
  at least one cast *to* the wire dtype to exist — a wire flag that
  quantizes nothing is a silently-dead contract.  EF modes are audited
  through the engines' ``ef=`` API with a zero residual.

Entry points are registered in :data:`TRACE_SPECS`; each spec is traced
with tiny shapes (seconds, no device execution).  Wire modes are spelled
``"wire"`` (bf16) or ``"wire:int8"`` / ``"wire:fp8"``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List, Optional, Sequence

from .report import PassResult


def _walk(jaxpr) -> Iterator[object]:
    """All equations of a jaxpr, recursing into sub-jaxprs (pjit, scan,
    cond, while, custom_*, and pallas_call kernel bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk(sub)


def _subjaxprs(v) -> Iterator[object]:
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _subjaxprs(item)


def _dtypes(vars_, *, literals: bool = False):
    import jax.numpy as jnp
    from jax.extend.core import Literal
    out = []
    for var in vars_:
        if not literals and isinstance(var, Literal):
            continue
        aval = getattr(var, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            out.append(jnp.dtype(dt))
    return out


def _float_dtypes(vars_, *, literals: bool = False):
    import jax.numpy as jnp
    return [dt for dt in _dtypes(vars_, literals=literals)
            if jnp.issubdtype(dt, jnp.floating)]


def audit_f64(jaxpr) -> List[str]:
    """Equations where an f64 operand flows into a narrower float output."""
    import numpy as np
    bad = []
    for eqn in _walk(jaxpr):
        ins = _float_dtypes(eqn.invars)
        if not any(dt == np.float64 for dt in ins):
            continue
        outs = _float_dtypes(eqn.outvars, literals=True)
        narrow = [dt for dt in outs if dt.itemsize < 8]
        if narrow:
            bad.append(f"{eqn.primitive.name}: f64 operand -> "
                       f"{'/'.join(d.name for d in narrow)} output")
    return bad


#: Wire-mode name -> the jnp dtype that travels on the wire.
def _wire_np_dtype(wire: str):
    import jax.numpy as jnp
    import numpy as np
    return np.dtype({"bf16": jnp.bfloat16, "int8": jnp.int8,
                     "fp8": jnp.float8_e4m3fn}[wire])


def audit_wire(jaxpr, wire: str = "bf16") -> List[str]:
    """Wire-accumulation violations in a wire-mode jaxpr (plus a no-op
    check: the trace must actually contain a cast *to* the wire dtype).

    An equation that consumes the wire dtype and produces a sub-fp32
    *float* accumulates in (or near) wire precision — only the
    quantize/dequantize ``convert_element_type`` casts may touch it.
    Pure-layout ops on the quantized payload (reshape/broadcast keeping
    the wire dtype) are not accumulation and pass.
    """
    wire_dt = _wire_np_dtype(wire)
    bad, n_quantize = [], 0
    for eqn in _walk(jaxpr):
        if eqn.primitive.name == "convert_element_type":
            if any(dt == wire_dt
                   for dt in _dtypes(eqn.outvars, literals=True)):
                n_quantize += 1
            continue        # the quantize/dequantize casts themselves
        ins = _dtypes(eqn.invars)
        if not any(dt == wire_dt for dt in ins):
            continue
        narrow = [dt for dt in _float_dtypes(eqn.outvars, literals=True)
                  if dt.itemsize < 4]
        if narrow:
            bad.append(
                f"{eqn.primitive.name}: accumulates {wire} operand in "
                f"{'/'.join(d.name for d in narrow)} (needs fp32+)")
    if n_quantize == 0:
        bad.append(f"wire mode traced but no {wire} quantize cast found — "
                   "the wire_dtype flag is a no-op on this path")
    return bad


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """One auditable entry point.

    ``build(dtype)`` returns ``(fn, args)``; the audit runs
    ``jax.make_jaxpr(fn)(*args)``.  ``modes`` picks which contracts apply:
    ``"f64"`` traces under x64 with f64 inputs, ``"wire"`` traces an
    explicitly wire-enabled configuration with f32 inputs.
    """

    name: str
    build: Callable
    modes: Sequence[str] = ("f64",)


# ---------------------------------------------------------------- builders
def _problem(dtype, m=4, d=16, k=3, seed=0):
    import jax.numpy as jnp
    import numpy as np
    from repro.core.operators import StackedOperators
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, 8, d))
    ops = StackedOperators(data=jnp.asarray(X, dtype))
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0], dtype)
    return ops, W0


def _dense_problem(dtype, m=4, d=16, k=3, seed=0):
    import jax.numpy as jnp
    import numpy as np
    from repro.core.operators import StackedOperators
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, 8, d))
    A = np.einsum("mnd,mne->mde", X, X) / 8.0
    ops = StackedOperators(dense=jnp.asarray(A, dtype))
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0], dtype)
    return ops, W0


def _carry(ops, W0):
    import jax.numpy as jnp
    W = jnp.broadcast_to(W0, (ops.m,) + W0.shape).astype(W0.dtype)
    return (W, W, W)


def _topology(m=4):
    from repro.core.topology import ring
    return ring(m)


def _schedule(m=4):
    from repro.core.schedule import TopologySchedule
    from repro.core.topology import complete, ring
    return TopologySchedule.piecewise([(0, ring(m)), (2, complete(m))])


def _build_deepca(dtype):
    from repro.core.algorithms import deepca

    def fn(arr, W0, U):
        from repro.core.operators import StackedOperators
        res = deepca(StackedOperators(data=arr), _topology(), W0,
                     k=W0.shape[-1], T=2, K=3, U=U)
        return res.W, res.trace.mean_tan_theta

    ops, W0 = _problem(dtype)
    U = W0  # any orthonormal (d, k) works for tracing the metric path
    return fn, (ops.array, W0, U)


def _build_deepca_schedule(dtype):
    from repro.core.algorithms import deepca

    def fn(arr, W0, U):
        from repro.core.operators import StackedOperators
        res = deepca(StackedOperators(data=arr), None, W0, k=W0.shape[-1],
                     T=3, K=2, U=U, schedule=_schedule())
        return res.W

    ops, W0 = _problem(dtype)
    return fn, (ops.array, W0, W0)


def _build_depca_increasing(dtype):
    from repro.core.algorithms import depca

    def fn(arr, W0, U):
        from repro.core.operators import StackedOperators
        res = depca(StackedOperators(data=arr), _topology(), W0,
                    k=W0.shape[-1], T=2, K=1, U=U,
                    increasing_consensus=True)
        return res.W

    ops, W0 = _problem(dtype)
    return fn, (ops.array, W0, W0)


def _build_run_batch(dtype):
    from repro.core.consensus import ConsensusEngine
    from repro.core.driver import IterationDriver
    from repro.core.step import PowerStep
    import jax.numpy as jnp

    eng = ConsensusEngine(topology=_topology(), K=2, backend="stacked")
    driver = IterationDriver(step=PowerStep(track=True, rounds=2),
                             engine=eng)

    def fn(arr, W0):
        from repro.core.operators import StackedOperators
        out = driver.run_batch(StackedOperators(data=arr), W0, T=2)
        return out.S, out.W

    ops, W0 = _problem(dtype)
    B = 2
    arr = jnp.stack([ops.array, ops.array])
    W0b = jnp.stack([W0] * B)
    return fn, (arr, W0b)


def _build_driver_run(dtype):
    """driver.run + resumed window (the run_stream per-tick program)."""
    from repro.core.consensus import ConsensusEngine
    from repro.core.driver import IterationDriver
    from repro.core.step import PowerStep

    eng = ConsensusEngine(topology=_topology(), K=2, backend="stacked")
    driver = IterationDriver(step=PowerStep(track=True, rounds=2),
                             engine=eng)
    ops, W0 = _problem(dtype)
    fn, _warm = driver.program(
        2, "data", driver._apply_form("data", ops.array, 2))
    return (lambda arr, w0, *carry: fn(arr, w0, None, *carry)), \
        (ops.array, W0) + tuple(_carry(ops, W0))


def _engine(dtype, backend, wire=None, interpret=None):
    from repro.core.consensus import ConsensusEngine
    return ConsensusEngine(topology=_topology(), K=2, backend=backend,
                           wire_dtype=wire, interpret=interpret)


def _build_engine_mix(backend, wire=None, interpret=None):
    def build(dtype):
        eng = _engine(dtype, backend, wire, interpret)
        ops, W0 = _problem(dtype)
        S = _carry(ops, W0)[0]
        return (lambda x: eng.mix(x)), (S,)
    return build


def _build_engine_mix_track(backend, wire=None, interpret=None):
    def build(dtype):
        eng = _engine(dtype, backend, wire, interpret)
        ops, W0 = _problem(dtype)
        S, W, Gp = _carry(ops, W0)
        G = ops.apply(W)
        return (lambda s, g, gp: eng.mix_track(s, g, gp)), (S, G, Gp)
    return build


def _build_engine_apply_mix_track(backend, wire=None, interpret=None):
    def build(dtype):
        eng = _engine(dtype, backend, wire, interpret)
        ops, W0 = _dense_problem(dtype)
        S, W, Gp = _carry(ops, W0)

        def fn(arr, s, w, gp):
            from repro.core.operators import StackedOperators
            return eng.apply_mix_track(s, w, gp,
                                       StackedOperators(dense=arr))

        return fn, (ops.array, S, W, Gp)
    return build


def _build_dynamic_mix_track(backend, wire=None, interpret=None):
    def build(dtype):
        from repro.core.consensus import DynamicConsensusEngine
        dyn = DynamicConsensusEngine(schedule=_schedule(), K=2,
                                     backend=backend, wire_dtype=wire,
                                     interpret=interpret)
        ops, W0 = _problem(dtype)
        S, W, Gp = _carry(ops, W0)
        G = ops.apply(W)
        Ls, etas = dyn.operands(0, 1, dtype=S.dtype)
        eng = dyn.engine_at(0)
        return (lambda s, g, gp, L, eta:
                eng.mix_track(s, g, gp, mixing=(L, eta))), \
            (S, G, Gp, Ls[0], etas[0])
    return build


def _build_fastmix_wire(dtype):
    import jax.numpy as jnp
    from repro.core.mixing import fastmix_wire
    ops, W0 = _problem(dtype)
    S = _carry(ops, W0)[0]
    L = jnp.asarray(_topology().mixing, dtype)
    return (lambda s, l: fastmix_wire(s, l, 0.5, 3)), (S, L)


# EF-wire builders: the engines' ef= API with a zero residual (the state a
# fresh carry starts from); [0] keeps only the mixed iterate so the audit
# sees exactly what a driver step consumes.
def _build_engine_mix_ef(backend, wire, interpret=None):
    def build(dtype):
        import jax.numpy as jnp
        eng = _engine(dtype, backend, wire, interpret)
        ops, W0 = _problem(dtype)
        S = _carry(ops, W0)[0]
        return (lambda s, e: eng.mix(s, ef=e)[0]), (S, jnp.zeros_like(S))
    return build


def _build_engine_mix_track_ef(backend, wire, interpret=None):
    def build(dtype):
        import jax.numpy as jnp
        eng = _engine(dtype, backend, wire, interpret)
        ops, W0 = _problem(dtype)
        S, W, Gp = _carry(ops, W0)
        G = ops.apply(W)
        return (lambda s, g, gp, e: eng.mix_track(s, g, gp, ef=e)[0]), \
            (S, G, Gp, jnp.zeros_like(S))
    return build


def _build_dynamic_mix_track_ef(backend, wire, interpret=None):
    def build(dtype):
        import jax.numpy as jnp
        from repro.core.consensus import DynamicConsensusEngine
        dyn = DynamicConsensusEngine(schedule=_schedule(), K=2,
                                     backend=backend, wire_dtype=wire,
                                     interpret=interpret)
        ops, W0 = _problem(dtype)
        S, W, Gp = _carry(ops, W0)
        G = ops.apply(W)
        Ls, etas = dyn.operands(0, 1, dtype=S.dtype)
        eng = dyn.engine_at(0)
        return (lambda s, g, gp, L, eta, e:
                eng.mix_track(s, g, gp, ef=e, mixing=(L, eta))[0]), \
            (S, G, Gp, Ls[0], etas[0], jnp.zeros_like(S))
    return build


def _build_fastmix_wire_ef(wire):
    def build(dtype):
        import jax.numpy as jnp
        from repro.core.mixing import fastmix_wire_ef
        ops, W0 = _problem(dtype)
        S = _carry(ops, W0)[0]
        L = jnp.asarray(_topology().mixing, dtype)
        return (lambda s, e, l:
                fastmix_wire_ef(s, e, l, 0.5, 3, wire_dtype=wire)), \
            (S, jnp.zeros_like(S), L)
    return build


TRACE_SPECS = (
    TraceSpec("deepca[scan,stacked]", _build_deepca, ("f64",)),
    TraceSpec("deepca[schedule,traced_scan]", _build_deepca_schedule,
              ("f64",)),
    TraceSpec("depca[unrolled,increasing]", _build_depca_increasing,
              ("f64",)),
    TraceSpec("driver.run_batch[stacked]", _build_run_batch, ("f64",)),
    TraceSpec("driver.run[scan program]", _build_driver_run, ("f64",)),
    TraceSpec("engine.mix[stacked]", _build_engine_mix("stacked"), ("f64",)),
    TraceSpec("engine.mix[pallas]",
              _build_engine_mix("pallas", interpret=True), ("f64",)),
    TraceSpec("engine.mix_track[stacked]",
              _build_engine_mix_track("stacked"), ("f64",)),
    TraceSpec("engine.mix_track[pallas]",
              _build_engine_mix_track("pallas", interpret=True), ("f64",)),
    TraceSpec("engine.apply_mix_track[stacked]",
              _build_engine_apply_mix_track("stacked"), ("f64",)),
    TraceSpec("engine.apply_mix_track[pallas]",
              _build_engine_apply_mix_track("pallas", interpret=True),
              ("f64",)),
    TraceSpec("engine.mix_track[pallas,traced]",
              _build_dynamic_mix_track("pallas", interpret=True), ("f64",)),
    # wire-precision paths: every bf16 configuration the engines expose
    TraceSpec("engine.mix[stacked,wire]",
              _build_engine_mix("stacked", wire="bf16"), ("wire",)),
    TraceSpec("engine.mix[pallas,wire]",
              _build_engine_mix("pallas", wire="bf16", interpret=True),
              ("wire",)),
    TraceSpec("engine.mix_track[stacked,wire]",
              _build_engine_mix_track("stacked", wire="bf16"), ("wire",)),
    TraceSpec("engine.mix_track[pallas,wire]",
              _build_engine_mix_track("pallas", wire="bf16",
                                      interpret=True), ("wire",)),
    TraceSpec("engine.apply_mix_track[pallas,wire]",
              _build_engine_apply_mix_track("pallas", wire="bf16",
                                            interpret=True), ("wire",)),
    TraceSpec("engine.mix_track[pallas,traced,wire]",
              _build_dynamic_mix_track("pallas", wire="bf16",
                                       interpret=True), ("wire",)),
    TraceSpec("mixing.fastmix_wire", _build_fastmix_wire, ("wire",)),
    # EF wire paths: int8 always runs the stacked per-round reference
    # (per-agent scale is a cross-tile reduction); fp8 additionally has a
    # true in-kernel mirror on the pallas backends
    TraceSpec("engine.mix[stacked,int8]",
              _build_engine_mix_ef("stacked", "int8"), ("wire:int8",)),
    TraceSpec("engine.mix[pallas,fp8]",
              _build_engine_mix_ef("pallas", "fp8", interpret=True),
              ("wire:fp8",)),
    TraceSpec("engine.mix_track[stacked,int8]",
              _build_engine_mix_track_ef("stacked", "int8"),
              ("wire:int8",)),
    TraceSpec("engine.mix_track[pallas,fp8]",
              _build_engine_mix_track_ef("pallas", "fp8", interpret=True),
              ("wire:fp8",)),
    TraceSpec("engine.mix_track[pallas,traced,fp8]",
              _build_dynamic_mix_track_ef("pallas", "fp8", interpret=True),
              ("wire:fp8",)),
    TraceSpec("mixing.fastmix_wire_ef[int8]", _build_fastmix_wire_ef("int8"),
              ("wire:int8",)),
    TraceSpec("mixing.fastmix_wire_ef[fp8]", _build_fastmix_wire_ef("fp8"),
              ("wire:fp8",)),
)


def check_f64(fn, *args) -> List[str]:
    """Audit one callable's f64 trace (caller supplies f64 inputs)."""
    import jax
    return audit_f64(jax.make_jaxpr(fn)(*args).jaxpr)


def check_wire(fn, *args, wire: str = "bf16") -> List[str]:
    """Audit one callable's wire-mode trace (f32 inputs)."""
    import jax
    return audit_wire(jax.make_jaxpr(fn)(*args).jaxpr, wire=wire)


def run(names: Optional[Sequence[str]] = None) -> PassResult:
    """Trace and audit every registered entry point (or a name subset)."""
    import jax
    import jax.numpy as jnp

    result = PassResult(name="tracecheck")
    for spec in TRACE_SPECS:
        if names is not None and spec.name not in names:
            continue
        for mode in spec.modes:
            unit = f"{spec.name}<{mode}>"
            try:
                if mode == "f64":
                    with jax.enable_x64(True):
                        fn, args = spec.build(jnp.float64)
                        bad = audit_f64(jax.make_jaxpr(fn)(*args).jaxpr)
                else:
                    wire = mode.split(":", 1)[1] if ":" in mode else "bf16"
                    fn, args = spec.build(jnp.float32)
                    bad = audit_wire(jax.make_jaxpr(fn)(*args).jaxpr,
                                     wire=wire)
            except Exception as e:            # tracing itself must not break
                result.add("trace-error", unit, 0,
                           f"failed to trace: {type(e).__name__}: {e}")
                continue
            result.checked += 1
            code = "f64-narrowing" if mode == "f64" else "wire-accumulation"
            for msg in bad:
                result.add(code, unit, 0, msg)
    return result
