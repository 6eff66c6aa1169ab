"""95th percentile of every gap between consecutive output tokens of the
requests due in the window, in milliseconds (host clock): a decode step,
or a decode step that follows a prefill."""


def read(ctx):
    return ctx["served"]["serve_tpot_p95_ms"]
