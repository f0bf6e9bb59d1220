"""How full the micro-batcher's flushes ran in the window: requests
flushed / (flushes x batch cap), as deltas over the window."""


def read(view):
    c = view.counters
    if not c:
        return None
    flushes = sum(v for k, v in c.items()
                  if k.startswith("serve.batch.flushes."))
    if not flushes:
        return None
    return c["serve.batch.flushed_requests"] / (flushes * c["max_batch"])
