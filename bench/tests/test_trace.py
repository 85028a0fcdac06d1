"""The trace reduction: on hand-made traces whose numbers are known, and on
a small trace recorded on a TPU v5e chip (``data/chip_trace.json.gz``)."""
import os

import pytest

import tracereduce as tr

MS = 1_000_000
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_trace():
    """Window 0-100 ms.  Device 0: a fusion 10-20, the kernel 15-30
    (overlapping it), a collective 40-60 of which 50-55 overlaps compute.
    Device 1: one fusion 0-50.  Host spans: tick 0-45, poll 30-45 inside
    it, result 45-100."""
    d0 = [("fusion.1", 10 * MS, 20 * MS, "fusion.1"),
          ("custom-call.7", 15 * MS, 30 * MS,
           "custom-call.7 kernel=_fastmix_track_kernel"),
          ("collective-permute-done.1", 40 * MS, 60 * MS,
           "collective-permute-done.1"),
          ("fusion.2", 50 * MS, 55 * MS, "fusion.2"),
          ("fusion.3", 120 * MS, 130 * MS, "fusion.3")]   # after the window
    d1 = [("fusion.9", 0, 50 * MS, "fusion.9")]
    spans = [("bench.window", 0, 100 * MS), ("bench.tick", 0, 45 * MS),
             ("bench.poll", 30 * MS, 45 * MS), ("bench.result", 45 * MS,
                                                100 * MS)]
    return tr.Trace(devices={"/device:TPU:0": d0, "/device:TPU:1": d1},
                    spans=spans)


def test_busy_is_the_union_inside_the_window():
    t = hand_trace()
    assert t.window_s() == pytest.approx(0.1)
    assert t.busy_s("/device:TPU:0") == pytest.approx(0.040)  # 10-30, 40-60
    assert t.busy_s("/device:TPU:1") == pytest.approx(0.050)
    assert t.mean_busy_s() == pytest.approx(0.045)
    assert t.idle_share() == pytest.approx(0.55)


def test_kernel_time():
    t = hand_trace()
    assert t.kernel_s("_fastmix_track_kernel") == pytest.approx(0.015)
    assert t.kernel_s("_gram_kernel") == 0.0


def test_idle_gaps_go_to_the_innermost_open_span():
    t = hand_trace()
    gaps = sorted(t.idle_gaps(), key=lambda g: (g[1], g[0]))
    # device 0: 0-10 (tick), 30-40 (poll, inside tick), 60-100 (result);
    # device 1: 50-100 (result)
    assert [(g[0], g[1] // MS, g[2] // MS) for g in gaps] == [
        ("bench.tick", 0, 10), ("bench.poll", 30, 40),
        ("bench.result", 50, 100), ("bench.result", 60, 100)]
    by_span = dict((k, v) for k, v in t.idle_by_span())
    assert by_span["bench.result"] == pytest.approx(0.090)
    # idle time charged to spans adds up to the window minus busy time
    total = sum(v for _, v in t.idle_by_span())
    assert total == pytest.approx(2 * 0.1 - 0.040 - 0.050)


def test_exposed_collective_time():
    t = hand_trace()
    # collective 40-60, compute 50-55 over it: 15 ms exposed
    assert t.exposed_collective_s("/device:TPU:0") == pytest.approx(0.015)
    assert t.collective_share() == pytest.approx(0.015 / 0.090)


def test_top_ops_sums_by_name():
    t = hand_trace()
    top = dict((k, v) for k, v in t.top_ops())
    assert top["fusion.9"] == pytest.approx(0.050)
    assert "fusion.3" not in top                    # outside the window
    assert len(t.top_ops(2)) == 2


def test_no_collective_reads_none():
    t = tr.Trace(devices={"d": [("fusion", 0, MS, "fusion")]},
                 spans=[("bench.window", 0, 2 * MS)])
    assert t.collective_share() is None


def test_subtract_ns():
    assert tr.subtract_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert tr.subtract_ns([(0, 10)], []) == 10
    assert tr.subtract_ns([(0, 10)], [(0, 10)]) == 0
    assert tr.subtract_ns([(0, 10)], [(2, 3), (4, 6)]) == 7


def test_save_and_load_round_trip(tmp_path):
    t = hand_trace()
    tr.save(t, str(tmp_path / "t.json.gz"))
    u = tr.load(str(tmp_path / "t.json.gz"))
    assert u.busy_s("/device:TPU:0") == t.busy_s("/device:TPU:0")
    assert u.idle_by_span() == t.idle_by_span()


# -------------------------------------------------------------- chip trace
# 60 ms of w8a-m50.single (random starts) recorded on one TPU v5 lite: the
# leaf XLA ops with their instruction names and the benchmark's host
# spans, cut to a window around two solves.  The numbers below were read
# from it when it was committed.
CHIP = os.path.join(DATA, "chip_trace.json.gz")


def test_chip_trace_reduces_to_its_recorded_numbers():
    t = tr.load(CHIP)
    assert list(t.devices) == ["/device:TPU:0"]
    assert t.window_s() == pytest.approx(0.060)
    assert t.mean_busy_s() == pytest.approx(0.02826713)
    assert t.kernel_s("_fastmix_track_fused") == pytest.approx(0.001853389)
    assert t.kernel_s("__gram_") == pytest.approx(0.008530608)
    idle = dict((k, v) for k, v in t.idle_by_span())
    assert set(idle) == {"bench.wait", "bench.poll", "bench.result"}
    assert idle["bench.wait"] == pytest.approx(0.024943451)
    assert t.collective_share() is None


def test_chip_trace_is_consistent():
    t = tr.load(CHIP)
    ops = t.ops("/device:TPU:0")
    # one tracking + FastMix launch per power iteration: two 40-iteration
    # solves, the window cutting into a third
    n_mix = sum("_fastmix_track_fused" in o[3] for o in ops)
    assert 80 >= n_mix >= 78
    # idle time charged to spans is the window less the busy time
    total_idle = sum(v for _, v in t.idle_by_span())
    assert total_idle == pytest.approx(t.window_s() - t.mean_busy_s())
    # the top ops are leaves (no while loop of a scan among them)
    assert all(not name.startswith("while") for name, _ in t.top_ops())
    assert 0.0 < t.idle_share() < 1.0


def test_leaves_drop_enclosing_ops():
    ops = [("while.1", 0, 100, "while.1"), ("fusion.1", 10, 20, "fusion.1"),
           ("fusion.2", 30, 60, "fusion.2"), ("copy.1", 120, 130, "copy.1")]
    assert [o[0] for o in tr._leaves(ops)] == ["fusion.1", "fusion.2",
                                               "copy.1"]
    assert tr.op_name("%fusion.301 = f32[50,300,8] fusion(...)") == \
        "fusion.301"
