"""The program's own spans (``repro.obs.trace``), read in the
benchmark process.

A program span is buffered while its tracer is enabled or a profiler
session records.  The benchmark enables no tracer and records only
the traced window, so in a ``--trace 1`` run the default tracer's
buffer holds the window's spans.  ``program_events`` reads that buffer
without draining it; the reductions take plain lists of events (the
Chrome "X" dicts the tracer buffers, durations in microseconds, with
``args.id`` and ``args.parent``), so that they can be tested without a
run.  Each returns None where it finds nothing to read.
"""
from __future__ import annotations


def program_events(prefix: str) -> list[dict]:
    """The default tracer's buffered events whose name starts with
    ``prefix``; empty where the program buffers none."""
    from repro.obs import trace

    return [e for e in trace.get_tracer().events()
            if e["name"].startswith(prefix)]


def self_us_per_item(events: list[dict], outer: str,
                     wait: str) -> float | None:
    """Host self time per item: the summed durations of the ``outer``
    spans less those of their ``wait`` descendants, over the summed
    ``n`` tags of the ``outer`` spans, in microseconds."""
    outers = {e["args"]["id"]: e for e in events if e["name"] == outer}
    items = sum(e["args"]["n"] for e in outers.values())
    if not items:
        return None
    parent = {e["args"]["id"]: e["args"].get("parent") for e in events}
    waited = 0.0
    for e in events:
        if e["name"] != wait:
            continue
        up = e["args"].get("parent")
        while up is not None and up not in outers:
            up = parent.get(up)
        if up is not None:
            waited += e["dur"]
    return (sum(e["dur"] for e in outers.values()) - waited) / items


def fill(events: list[dict], name: str) -> float | None:
    """Real rows over padded rows, summed over the ``name`` spans'
    ``n`` and ``padded`` tags."""
    padded = sum(e["args"]["padded"] for e in events if e["name"] == name)
    if not padded:
        return None
    return sum(e["args"]["n"] for e in events if e["name"] == name) \
        / padded
