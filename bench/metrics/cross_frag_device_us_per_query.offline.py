"""Device time of the planner's ``cross_frag`` program (``serve_cross``
without the local fold: the same-group gather loop and the top
combine) per query answered in the offline window, in microseconds:
the ``jit_planner_cross_frag`` module's time in the trace."""
MODULE = "jit_planner_cross_frag"


def read(view):
    tr = view.trace
    if not tr or not view.answered or not tr["modules"].get(MODULE):
        return None
    return 1e6 * tr["modules"][MODULE] / view.answered
