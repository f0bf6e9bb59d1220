"""Device time of the planner's serve programs per query answered in
the offline window, in microseconds: every XLA module that ran in the
window (the offline loop runs nothing else on the device) over the
queries answered.  The programs are all named ``jit__unknown`` in the
trace, so ``serve_cross`` cannot be told apart from the others yet."""


def read(view):
    tr = view.trace
    if not tr or not view.answered:
        return None
    s = sum(tr["modules"].values())
    if not s:
        return None
    return 1e6 * s / view.answered
