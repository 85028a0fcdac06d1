"""The device generators against the host originals they copy
(``repro.core.operators.libsvm_like``): same shapes and dtype, same
statistics."""
import numpy as np
import pytest

import harness


def top_evals(X, k):
    X = np.asarray(X, np.float64)
    m, n, d = X.shape
    flat = X.reshape(m * n, d)
    return np.sort(np.linalg.eigvalsh(flat.T @ flat / m))[::-1][:k]


def test_libsvm_like_matches_host_original():
    from repro.core.operators import libsvm_like
    gen = harness.generator("libsvm_like")
    m, n, d = 8, 4000, 40
    host = np.asarray(libsvm_like(m, n, d, seed=3).data)
    dev = np.asarray(gen.problem(harness.seed_key(3), m=m, n=n, d=d))
    assert dev.shape == host.shape and dev.dtype == host.dtype == np.float32
    # the sparse activations set the column means: same profile per agent
    # (the power law, rolled with the agent index)
    mh, md = host.mean(axis=1), dev.mean(axis=1)
    assert np.corrcoef(mh.ravel(), md.ravel())[0, 1] > 0.95
    assert md.mean() == pytest.approx(mh.mean(), rel=0.05)
    # the spiked structure sets the top of the mean covariance
    np.testing.assert_allclose(top_evals(dev, 6), top_evals(host, 6),
                               rtol=0.08)
