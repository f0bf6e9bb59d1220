"""Device time of the planner's ``cross_res`` program (the resident
path: pre-composed lifts and one fused twoside against the top
closure) per query answered in the offline window, in microseconds:
the ``jit_planner_cross_res`` module's time in the trace."""
MODULE = "jit_planner_cross_res"


def read(view):
    tr = view.trace
    if not tr or not view.answered or not tr["modules"].get(MODULE):
        return None
    return 1e6 * tr["modules"][MODULE] / view.answered
