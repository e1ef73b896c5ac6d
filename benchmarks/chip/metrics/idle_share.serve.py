"""idle_share.serve: share of the traced serving window in which no
operation ran on the device: 1 - busy / window, busy being the union of
the device's op intervals. Moves p95_latency_ms."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0 or not r.trace.ops:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
