"""Device time in which a collective operation (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute) was in flight, over the traced
stretch: the union on each device plane's `XLA Ops` line, a split operation
counted from its start's beginning to its done's end, averaged over the
planes (benchmarks/lib/trace.py:collective_intervals). A trace that holds no
collective (one chip) gives nothing."""

DECLARATION = {"name": "collective_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "gradient all-reduce (data axis)", "moves": "step_ms"}


def read(ctx):
    t = ctx["trace"]
    spent = t.collective_seconds if t is not None else None
    if spent is None:
        return None
    return 100.0 * spent[0] / t.window_s
