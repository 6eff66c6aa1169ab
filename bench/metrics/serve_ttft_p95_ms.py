"""95th percentile, over every request due in the window, of the time from
when it was due to its first token, in milliseconds (host clock).  Above
the knee the queue grows through the window, so this tail swings from run
to run: it is read, not bounded."""


def read(ctx):
    return ctx["served"]["serve_ttft_p95_ms"]
