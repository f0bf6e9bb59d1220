"""The trace reduction: busy union, idle share, per-program device time
and labelled idle gaps, on made-up events and on a short trace recorded
on a TPU v5e (``bench/tests/data/road16k_live.xplane.pb``)."""
import bench_tiny
import pytest

from bench import trace_reduce as tr

MS = 1_000_000    # ns


def _spans():
    return [("bench.window", 0, 100 * MS),
            ("bench.offline.batch", 10 * MS, 40 * MS),
            ("bench.offline.batch", 60 * MS, 40 * MS),
            ("other", 0, 100 * MS)]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [("fusion.1", 12 * MS, 10 * MS), ("fusion.2", 15 * MS, 10 * MS),
           ("while.3", 62 * MS, 30 * MS), ("copy", 95 * MS, 10 * MS),
           ("early", -5 * MS, 3 * MS)]
    mods = [("jit_serve_cross(7)", 12 * MS, 13 * MS),
            ("jit_serve_cross(9)", 62 * MS, 30 * MS),
            ("jit_serve_same_dra(3)", 95 * MS, 10 * MS)]
    red = tr.reduce_events({"/device:TPU:0": {tr.OPS_LINE: ops,
                                              tr.MODULES_LINE: mods}},
                           _spans())
    # [12, 25] + [62, 92] + [95, 100] clipped to the window
    assert red["busy_s"] == pytest.approx(0.048)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["modules"] == {"jit_serve_cross": pytest.approx(0.043)}
    assert red["ops"]["while.3"] == pytest.approx(0.03)
    assert "early" not in red["ops"]
    gaps = red["idle_gaps"]
    assert sum(s for _, s in gaps) == pytest.approx(0.1 - 0.048)
    # the longest gap, [25, 62], has its middle in the first batch span;
    # the first, [0, 12], in none
    assert gaps[0] == ("bench.offline.batch", pytest.approx(0.037))
    assert gaps[1] == ("outside bench spans", pytest.approx(0.012))
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["jit_serve_cross", pytest.approx(0.043)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_busy_is_averaged_over_devices():
    lines = {f"/device:TPU:{i}": {tr.OPS_LINE: [("op", 0, (i + 1) * 20 * MS)]}
             for i in range(2)}
    red = tr.reduce_events(lines, _spans())
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(0.03)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_events({}, [("bench.offline.batch", 0, MS)])


def test_a_trace_recorded_on_the_chip():
    """0.3 s of the road16k live window at 500 qps on one TPU v5e, cut
    to the lines the reduction reads (the device's ``XLA Ops`` and
    ``XLA Modules``, the host's ``bench.`` spans)."""
    red = tr.reduce_trace_file(
        str(bench_tiny.REPO / "bench/tests/data/road16k_live.xplane.pb"))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.302851269)
    assert red["busy_s"] == pytest.approx(0.005668851)
    # every planner program is named jit__unknown in the trace
    assert set(red["modules"]) == {"jit__unknown"}
    assert red["modules"]["jit__unknown"] == pytest.approx(
        red["busy_s"], rel=0.01)
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert red["idle_gaps"][0][0] == "bench.live.submit_loop"
    bd = tr.breakdown(red)
    assert bd["device_ops"][0][0] == "jit__unknown"
    assert all(len(name) <= tr.NAME_CHARS for name, _ in bd["device_ops"])
