"""The planner's per-layer readers: two read module times from the
trace's reduction, two read the program's own span buffer
(``bench/spans.py``).  Known values on synthetic input, nothing on
empty input."""
import bench_tiny
import pytest

from bench import cells, spans
from bench.run import View
from repro.obs import trace as trace_mod

READERS = {name: cells.load_module(
    bench_tiny.REPO / "bench" / "metrics" / f"{name}.py")
    for name in ("cross_frag_device_us_per_query.offline",
                 "cross_res_device_us_per_query.offline",
                 "serve_device_us_per_query.offline",
                 "planner_host_us_per_query.offline",
                 "bucket_fill.offline")}

RED = {"busy_s": 1.0, "window_s": 1.0, "devices": 1, "ops": {},
       "idle_gaps": [],
       "modules": {"jit_planner_cross_frag": 0.8,
                   "jit_planner_cross_res": 0.01,
                   "jit_planner_same_frag": 0.05,
                   "jit_planner_same_dra": 0.002}}


def _ev(name, sid, dur, parent=None, **tags):
    args = dict(tags, id=sid)
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur, "args": args}


#: two planner calls of 10 and 6 queries, and a wait outside any call
EVENTS = [
    _ev("planner.plan", 2, 5.0, 1),
    _ev("planner.device_wait", 4, 40.0, 3),
    _ev("planner.bucket", 3, 60.0, 1, case="cross_frag", n=6, padded=8),
    _ev("planner.device_wait", 6, 20.0, 5),
    _ev("planner.bucket", 5, 30.0, 1, case="same_dra", n=4, padded=4),
    _ev("planner.query", 1, 100.0, n=10),
    _ev("planner.device_wait", 9, 7.0, 8),
    _ev("planner.bucket", 8, 12.0, 7, case="hub", n=6, padded=16),
    _ev("planner.query", 7, 14.0, n=6),
    _ev("planner.device_wait", 10, 1000.0),
]


def test_module_readers_on_a_synthetic_reduction():
    view = View(trace=RED, counters=None, answered=1000)
    frag = READERS["cross_frag_device_us_per_query.offline"].read(view)
    res = READERS["cross_res_device_us_per_query.offline"].read(view)
    every = READERS["serve_device_us_per_query.offline"].read(view)
    assert frag == pytest.approx(800.0) and res == pytest.approx(10.0)
    assert frag + res <= every


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("view", [
    View(trace=None, counters=None, answered=0),
    View(trace=dict(RED, modules={}), counters=None, answered=1000),
    View(trace=RED, counters=None, answered=0),
], ids=["untraced", "no_modules", "nothing_answered"])
def test_readers_find_nothing_in_empty_input(name, view):
    trace_mod.get_tracer().clear()
    assert READERS[name].read(view) is None


def test_span_reductions_on_a_synthetic_buffer():
    # (100 - 40 - 20) + (14 - 7) over 16 queries; the stray wait is
    # no call's
    assert spans.self_us_per_item(EVENTS, "planner.query",
                                  "planner.device_wait") \
        == pytest.approx(47.0 / 16)
    assert spans.fill(EVENTS, "planner.bucket") == pytest.approx(16 / 28)
    assert spans.self_us_per_item([], "planner.query", "x") is None
    assert spans.fill(EVENTS[:2], "planner.bucket") is None


def test_span_readers_read_the_default_tracer():
    """The readers see the buffered spans of the process's default
    tracer, and leave the buffer as it was."""
    tr = trace_mod.get_tracer()
    tr.clear()
    tr.enable()
    try:
        with trace_mod.span("planner.query", n=3):
            with trace_mod.span("planner.bucket", case="x", n=3,
                                padded=16):
                with trace_mod.span("planner.device_wait"):
                    pass
        with trace_mod.span("serve.flush", size=3):
            pass
        events = tr.events()
        view = View(trace=RED, counters=None, answered=3)
        host = READERS["planner_host_us_per_query.offline"].read(view)
        fill = READERS["bucket_fill.offline"].read(view)
        assert tr.events() == events
    finally:
        tr.enable(False)
        tr.clear()
    q, b, w = (next(e for e in events if e["name"] == n)
               for n in ("planner.query", "planner.bucket",
                         "planner.device_wait"))
    assert host == pytest.approx((q["dur"] - w["dur"]) / 3)
    assert fill == pytest.approx(3 / 16)
