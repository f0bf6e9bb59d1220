"""Share of the live window in which no operation ran on the device:
1 - (union of device-op intervals) / window, from the profiler trace."""


def read(view):
    tr = view.trace
    if not tr or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
