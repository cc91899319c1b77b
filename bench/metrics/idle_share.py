"""idle_share.<kind>: the share of the traced window in which no operation
ran on the device, averaged over the chips: 100·(1 - busy/window), busy
being the union of the ``XLA Ops`` intervals (``trace_reduce``)."""


def read(name, m):
    if m["trace"] is None or not m["busy_s"]:
        return None
    return 100.0 * (1.0 - m["busy_s"] / m["window_s"])
