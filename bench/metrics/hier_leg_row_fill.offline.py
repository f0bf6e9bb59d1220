"""Share of the rows the same-group legs stream that do useful work: on
an accelerator each grouping level runs one masked (min,+) contraction
per real group over every row of a ``same_frag``/``cross_frag`` bucket,
and only the rows whose two sides share that group can win there.  The
``same_l<li>`` tags of the ``planner.bucket`` spans, summed, over their
``leg_rows`` tags.  Read from the program's span buffer
(``bench/spans.py``), which holds spans only in a traced run; a program
whose spans carry no ``leg_rows`` gives nothing."""
from bench import spans


def leg_row_fill(events: list[dict]) -> float | None:
    """Summed ``same_l<li>`` tags over summed ``leg_rows`` tags of the
    ``planner.bucket`` events; None where no event has ``leg_rows``."""
    legs = [e["args"] for e in events
            if e["name"] == "planner.bucket" and "leg_rows" in e["args"]]
    rows = sum(a["leg_rows"] for a in legs)
    if not rows:
        return None
    return sum(v for a in legs for k, v in a.items()
               if k.startswith("same_l")) / rows


def read(view):
    if view.trace is None:
        return None
    return leg_row_fill(spans.program_events("planner.bucket"))
