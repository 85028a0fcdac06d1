"""The roofline work functions against counts made by hand."""
import pytest

import harness
import work

V5E = harness.peaks("TPU v5 lite")


def test_fastmix_track_w8a_shape():
    # m=50, d*k=1500, K=8: 2*50*1500 + 8*(2*50*50*1500 + 3*50*1500)
    w = work.fastmix_track(50, 300, 5, 8)
    assert w.flops == 150_000 + 8 * (7_500_000 + 225_000) == 61_950_000
    # S, G, G_prev read and S written: 4 * 50 * 1500 * 4 bytes
    assert w.bytes == 1_200_000
    assert w.bound(V5E) == "bytes"
    assert w.least_s(V5E) == pytest.approx(1_200_000 / 819e9)


def test_fastmix_track_fleet_shape():
    # m=16, d*k=4096, K=6
    w = work.fastmix_track(16, 512, 8, 6)
    assert w.flops == 131_072 + 6 * (2_097_152 + 196_608) == 13_893_632
    assert w.bytes == 1_048_576
    assert w.bound(V5E) == "bytes"


def test_gram_w8a_shape():
    # one agent: X (300, 5) read once, (5, 5) written; 2*300*25 operations
    g = work.gram(300, 5)
    assert (g.flops, g.bytes) == (15_000, 6_100)
    # two Grams per agent per iteration, 50 agents
    it = work.gram_per_iteration(50, 300, 5)
    assert (it.flops, it.bytes) == (1_500_000, 610_000)


def test_roofline_share():
    w = work.Work(flops=0.0, bytes=819e9)      # one second of HBM traffic
    assert work.roofline_share(w, 2.0, V5E) == pytest.approx(50.0)
    assert work.roofline_share(w, 0.0, V5E) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
