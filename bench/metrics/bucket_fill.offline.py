"""Share of the rows the planner dispatched in the offline window that
were real queries and not pow2 padding: the ``n`` tags over the
``padded`` tags of the ``planner.bucket`` spans.  Read from the
program's span buffer (``bench/spans.py``), which holds spans only in
a traced run."""
from bench import spans


def read(view):
    if view.trace is None:
        return None
    return spans.fill(spans.program_events("planner.bucket"),
                      "planner.bucket")
