"""Host time of the planner per query in the offline window, in
microseconds: the ``planner.query`` spans less the
``planner.device_wait`` spans inside them (bucketing, padding, copies
to the device, dispatch, scatter), over the queries those calls
carried.  Read from the program's span buffer (``bench/spans.py``),
which holds spans only in a traced run."""
from bench import spans


def read(view):
    if view.trace is None:
        return None
    return spans.self_us_per_item(spans.program_events("planner."),
                                  "planner.query", "planner.device_wait")
