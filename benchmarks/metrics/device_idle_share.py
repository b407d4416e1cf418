"""Device idle share: 100 x (1 - busy / window), busy being the union of
the device's op intervals in the profiler trace of the window."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
