"""The span API's two sinks (DESIGN.md §16): the tracer's buffer, with
an ``id`` and the ``parent`` of every event, and a recording JAX
profiler session, into which every live span is mirrored as a
``TraceAnnotation`` on the device trace's clock."""
import glob
import os
import time

import jax
import pytest

from repro.obs import Tracer
from repro.obs import trace as trace_mod


def _start(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def _host_events(log_dir) -> list:
    """(name, start_ns, end_ns, stats) of every host-plane event."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]
    return out


def test_events_carry_id_and_parent():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("mid"):
            with tr.span("leaf"):
                pass
        with tr.span("mid2"):
            pass
    tr.event("posthoc", 0.0, 1.0)
    ev = {e["name"]: e["args"] for e in tr.events()}
    assert len({a["id"] for a in ev.values()}) == 5      # unique ids
    assert "parent" not in ev["outer"]
    assert ev["mid"]["parent"] == ev["outer"]["id"]
    assert ev["mid2"]["parent"] == ev["outer"]["id"]
    assert ev["leaf"]["parent"] == ev["mid"]["id"]
    assert ev["leaf"]["depth"] == 2
    # a post-hoc event was measured elsewhere: no enclosing span
    assert "parent" not in ev["posthoc"] and "depth" not in ev["posthoc"]


def test_a_recording_profiler_makes_spans_live(tmp_path):
    """A disabled tracer's spans are live while the profiler records
    and only then; ``event()`` stays buffer-only and off."""
    tr = Tracer(enabled=False)
    assert trace_mod.span("x") is tr.span("y")           # the singleton
    _start(tmp_path)
    try:
        assert jax.profiler.TraceAnnotation.is_enabled()
        with tr.span("live", k=1):
            pass
        out = {}
        with tr.timed("timed", out, "stage"):
            time.sleep(0.001)
        tr.event("posthoc", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    assert out["stage"] >= 0.001
    assert [e["name"] for e in tr.events()] == ["live", "timed"]
    assert tr.span("after") is trace_mod._NULL_SPAN
    with tr.span("after"):
        pass
    assert len(tr.events()) == 2


def test_mirrored_spans_nest_on_the_profiler_host_plane(tmp_path):
    tr = Tracer(enabled=False)
    _start(tmp_path)
    try:
        with jax.profiler.TraceAnnotation("enclosing"):
            with tr.span("mirror.outer", n=7, case="c"):
                with tr.timed("mirror.inner", None, None):
                    time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    got = {n: (a, b, st) for n, a, b, st in _host_events(tmp_path)
           if n in ("enclosing", "mirror.outer", "mirror.inner")}
    assert set(got) == {"enclosing", "mirror.outer", "mirror.inner"}
    (ea, eb, _), (oa, ob, ost), (ia, ib, _) = (
        got["enclosing"], got["mirror.outer"], got["mirror.inner"])
    assert ea <= oa <= ia and ib <= ob <= eb
    assert ib - ia >= 1_000_000                          # the sleep
    assert ost["n"] == 7 and ost["case"] == "c"          # tags as stats


@pytest.mark.parametrize("enabled", [False, True])
def test_span_stack_unwinds_on_error(enabled, tmp_path):
    """A span left by an exception closes: the next span is a root
    again, whichever sink made the span live."""
    tr = Tracer(enabled=enabled)
    if not enabled:
        _start(tmp_path)
    try:
        with pytest.raises(ValueError):
            with tr.span("fails"):
                raise ValueError
        assert tr.depth == 0
        with tr.span("next"):
            pass
    finally:
        if not enabled:
            jax.profiler.stop_trace()
    ev = {e["name"]: e["args"] for e in tr.events()}
    assert "parent" not in ev["next"]
