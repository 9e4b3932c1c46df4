"""device_idle_pct: the share of the device slice's window in which no
program runs on the device, 100 * (1 - busy / window), from the TPU
plane's program executions."""


def read(trace):
    s = trace.slice
    if s is None or s.window_ns <= 0:
        return None
    return 100.0 * (1.0 - s.busy_ns() / s.window_ns)
