"""Share of the serving window in which no operation runs on the device
(1 - busy / window), in percent."""
from bench import trace as tr


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_ns(t) / tr.window_ns(t))
