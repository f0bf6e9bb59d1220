"""Host wall time of the planner tier per query it answered, over the
window: delta of ``serve.tier.planner.seconds`` over the delta of
``serve.tier.planner.dispatches``, in microseconds."""


def read(view):
    c = view.counters
    if not c or not c.get("serve.tier.planner.dispatches"):
        return None
    return 1e6 * c["serve.tier.planner.seconds"] \
        / c["serve.tier.planner.dispatches"]
