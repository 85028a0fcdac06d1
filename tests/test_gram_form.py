"""The Gram form of the data-form apply in ``run_batch``.

A data operator's power step ``A_j W_j`` with ``A_j = X_j^T X_j`` can be
evaluated as ``X_j^T (X_j W_j)`` (the rows, read twice an iteration) or as
``(X_j^T X_j) W_j`` (the Gram, formed once per launch).  One shape rule,
:func:`gram_form_pays`, picks the cheaper order over a launch of ``T``
iterations, and every single-device launch follows it: ``run``'s
substrates and ``run_batch``, cold or resumed.  The contracts pinned here:
the rule's worked cases, the Gram form's answers (equal to the dense form
of the same Grams, and to the data form in float64), the data form kept
bit for bit where the rule declines, both schedule kinds engaging the
rule, one form for ``run`` and ``run_batch``, and warm launches staying
warm.
"""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import retrace
from repro.core import (ConsensusEngine, DynamicConsensusEngine,
                        IterationDriver, PowerStep, TopologySchedule,
                        erdos_renyi)
from repro.core.operators import StackedOperators, data_gram, gram_form_pays
from repro.runtime import telemetry, tracing


@pytest.mark.parametrize("n, d, T, pays", [
    (1008, 300, 40, True),      # w8a: 995 rows padded to 1008, d=300
    (64, 512, 3, False),        # the fleet's first bucket
    (80, 512, 3, False),        # the fleet's second bucket
    (1024, 1024, 30, True),     # chip_smoke phase b
    (8, 64, 3, False),
    (64, 16, 12, True),
    (8192, 8192, 30, False),    # forming costs more than the rows' reads
    (16384, 16384, 30, False),
    (8192, 4096, 20, False),    # fewer words, but the formation's MACs
    (600, 1000, 100, False),    # fewer words, but Grams larger than rows
])
def test_gram_form_pays_worked_cases(n, d, T, pays):
    assert gram_form_pays(n, d, T) is pays


def _problems(B, m, n, d, k, dtype, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((B, m, n, d)) / np.sqrt(n)
    W0 = np.stack([np.linalg.qr(rng.standard_normal((d, k)))[0]
                   for _ in range(B)])
    return jnp.asarray(X, dtype), jnp.asarray(W0, dtype)


def _driver(m, K=3, dynamic=False):
    step = PowerStep.for_algorithm("deepca", K)
    if dynamic:
        sched = TopologySchedule.periodic_rewiring(m, p=0.6, seed=0,
                                                   period=2)
        return IterationDriver(step=step, dynamic=DynamicConsensusEngine
                               .for_algorithm("deepca", sched, K=K,
                                              backend="stacked"))
    return IterationDriver(step=step, engine=ConsensusEngine.for_algorithm(
        "deepca", erdos_renyi(m, p=0.6, seed=2), K=K, backend="stacked"))


def _forms(rec):
    return [e.get("form") for e in rec.of("launch")
            if e["source"] == "driver.run_batch"]


def _dense(X):
    return StackedOperators(dense=jax.vmap(data_gram)(X))


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
def test_gram_form_matches_dense_grams(dynamic):
    """Where the rule engages, the data batch's answers are those of the
    same batch given as dense Grams (float32), for a static engine and a
    dynamic schedule alike."""
    B, m, n, d, k, T = 3, 8, 64, 16, 3, 12
    assert gram_form_pays(n, d, T)
    X, W0 = _problems(B, m, n, d, k, jnp.float32)
    driver = _driver(m, dynamic=dynamic)
    t0 = [0, 1, 2] if dynamic else None
    with telemetry.capture() as rec:
        out = driver.run_batch(StackedOperators(data=X), W0, T=T, t0=t0)
        ref = driver.run_batch(_dense(X), W0, T=T, t0=t0)
    assert _forms(rec) == ["gram", None]
    for a, b in zip(out.carries, ref.carries):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)


def test_gram_form_matches_data_form_in_float64():
    """In float64 the two evaluation orders agree to 1e-10: the Gram form
    changes the association, not the arithmetic."""
    B, m, n, d, k, T = 3, 8, 64, 16, 3, 12
    with jax.enable_x64(True):
        X, W0 = _problems(B, m, n, d, k, jnp.float64)
        driver = _driver(m)
        with telemetry.capture() as rec:
            out = driver.run_batch(StackedOperators(data=X), W0, T=T)
        assert _forms(rec) == ["gram"]
        for b in range(B):
            ref = driver.run(StackedOperators(data=X[b]), W0[b], T=T)
            for x, y in zip(out.carries, ref.carry):
                assert x.dtype == jnp.float64
                np.testing.assert_allclose(np.asarray(x[b]), np.asarray(y),
                                           rtol=0, atol=1e-10)


def test_data_form_kept_bitwise_where_the_rule_declines():
    """At a fleet-like shape (n much smaller than d, T=3) the cold launch
    and a resumed window are the data-form program: bit for bit the solo
    driver's answers."""
    B, m, n, d, k, T = 2, 6, 8, 64, 3, 3
    assert not gram_form_pays(n, d, T)
    X, W0 = _problems(B, m, n, d, k, jnp.float32, seed=1)
    driver = _driver(m)
    with telemetry.capture() as rec:
        cold = driver.run_batch(StackedOperators(data=X), W0, T=T)
        resumed = driver.run_batch(StackedOperators(data=X), W0, T=T,
                                   carry=cold.carries)
    assert _forms(rec) == ["data", "data"]
    for b in range(B):
        ops = StackedOperators(data=X[b])
        first = driver.run(ops, W0[b], T=T)
        second = driver.run(ops, W0[b], T=T, t0=T, carry=first.carry)
        for x, y in zip(cold.carries, first.carry):
            np.testing.assert_array_equal(np.asarray(x[b]), np.asarray(y))
        for x, y in zip(resumed.carries, second.carry):
            np.testing.assert_array_equal(np.asarray(x[b]), np.asarray(y))


def test_resumed_windows_take_the_rule_form():
    """A resumed window takes the form of a cold launch at the same shapes
    and ``T``, and matches the solo driver's resumed ``run`` bit for bit,
    so a fleet tenant's windows are its solo tracker's."""
    B, m, n, d, k, T = 2, 6, 64, 16, 3, 12
    X, W0 = _problems(B, m, n, d, k, jnp.float32, seed=2)
    driver = _driver(m)
    with telemetry.capture() as rec:
        cold = driver.run_batch(StackedOperators(data=X), W0, T=T)
        out = driver.run_batch(StackedOperators(data=X), W0, T=T,
                               carry=cold.carries)
    assert _forms(rec) == ["gram", "gram"]
    for b in range(B):
        ops = StackedOperators(data=X[b])
        first = driver.run(ops, W0[b], T=T)
        ref = driver.run(ops, W0[b], T=T, t0=T, carry=first.carry)
        for x, y in zip(out.carries, ref.carry):
            np.testing.assert_array_equal(np.asarray(x[b]), np.asarray(y))


@pytest.mark.parametrize("substrate", ["scan", "traced_scan", "unrolled"])
@pytest.mark.parametrize("n, d, T, form", [(64, 16, 12, "gram"),
                                           (8, 64, 3, "data")])
def test_run_substrates_take_the_rule_form(substrate, n, d, T, form,
                                           tmp_path):
    """Every ``run`` substrate takes the form ``run_batch`` takes at the
    same shapes and ``T``, and names it on its ``driver.launch`` span."""
    m, k = 6, 3
    X, W0 = _problems(1, m, n, d, k, jnp.float32, seed=3)
    driver = _driver(m, dynamic=substrate == "traced_scan")
    tracer = tracing.ChromeTracer(str(tmp_path / "r.json"))
    tracing.set_tracer(tracer)
    try:
        with telemetry.capture() as rec:
            driver.run(StackedOperators(data=X[0]), W0[0], T=T,
                       substrate=substrate)
            driver.run_batch(StackedOperators(data=X), W0, T=T,
                             t0=[0] if substrate == "traced_scan" else None)
    finally:
        tracing.set_tracer(None)
    launches = [e["args"] for e in json.loads(open(tracer.save()).read())[
        "traceEvents"] if e["name"] == "driver.launch"]
    assert [a.get("form") for a in launches] == [form, form]
    assert [e.get("form") for e in rec.of("launch")] == (
        [form] if substrate == "unrolled" else [form, form])


def test_run_batch_warm_bucket_stays_warm_in_gram_form():
    """The retrace contract's shapes select the Gram form, and a second
    launch at them compiles nothing."""
    assert gram_form_pays(20, 16, 2)     # _mini_problem rows, T=2
    contract = next(c for c in retrace.CONTRACTS
                    if c.name == "run-batch-warm-bucket")
    count, messages = retrace.measure(contract)
    assert count == 0, messages


def test_with_gram_validates_and_applies():
    X, W0 = _problems(1, 4, 12, 6, 2, jnp.float32)
    ops = StackedOperators(data=X[0])
    W = jnp.broadcast_to(W0[0], (4, 6, 2))
    formed = ops.with_gram()
    assert formed.dense is None and formed.gram.shape == (4, 6, 6)
    np.testing.assert_allclose(np.asarray(formed.apply(W)),
                               np.asarray(ops.apply(W)), rtol=0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(formed.mean_matrix()),
                                  np.asarray(ops.mean_matrix()))
    with pytest.raises(ValueError, match="data operator"):
        StackedOperators(dense=formed.gram).with_gram()
    with pytest.raises(ValueError, match="data operators only"):
        StackedOperators(dense=formed.gram, gram=formed.gram)
