"""The same-group legs' row fill, read from the program's span buffer
(``bench/spans.py``): a known value on recorded events, nothing on
empty input or where no bucket carries ``leg_rows``."""
import bench_tiny
import pytest

from bench import cells
from bench.run import View
from repro.obs import trace as trace_mod

READER = cells.load_module(
    bench_tiny.REPO / "bench" / "metrics" / "hier_leg_row_fill.offline.py")

RED = {"busy_s": 1.0, "window_s": 1.0, "devices": 1, "ops": {},
       "idle_gaps": [], "modules": {"jit_planner_cross_frag": 0.8}}


def _bucket(sid, **tags):
    return {"name": "planner.bucket", "ph": "X", "ts": 0.0, "dur": 9.0,
            "args": dict(tags, id=sid, parent=1)}


def test_leg_row_fill_on_recorded_events():
    """Summed same_l<li> tags over summed leg_rows tags of the leg
    buckets; buckets without leg_rows (another case, or a program that
    does not tag them) count for nothing, and none gives None."""
    other = [_bucket(3, case="cross_frag", n=6, padded=8),
             _bucket(5, case="hub", n=6, padded=16)]
    events = other + [
        _bucket(11, case="same_frag", n=7, padded=16, same_l0=7,
                same_l1=7, leg_rows=160),
        _bucket(12, case="cross_frag", n=286, padded=512, same_l0=194,
                same_l1=286, leg_rows=5120),
        _bucket(13, case="cross_frag", n=3, padded=4, same_l0=3)]
    fill = READER.leg_row_fill
    assert fill(events) == pytest.approx((7 + 7 + 194 + 286) / 5280)
    assert fill(other) is None and fill([]) is None
    assert fill(events[-1:]) is None


def test_leg_row_fill_reads_the_default_tracer():
    tr = trace_mod.get_tracer()
    tr.clear()
    tr.enable()
    try:
        with trace_mod.span("planner.bucket", case="cross_frag", n=3,
                            padded=16, same_l0=2, same_l1=3,
                            leg_rows=64):
            pass
        got = READER.read(View(trace=RED, counters=None, answered=3))
    finally:
        tr.enable(False)
        tr.clear()
    assert got == pytest.approx(5 / 64)


@pytest.mark.parametrize("view", [
    View(trace=None, counters=None, answered=0),
    View(trace=dict(RED, modules={}), counters=None, answered=1000),
    View(trace=RED, counters=None, answered=0),
], ids=["untraced", "no_modules", "nothing_answered"])
def test_leg_row_fill_finds_nothing_in_empty_input(view):
    trace_mod.get_tracer().clear()
    assert READER.read(view) is None
