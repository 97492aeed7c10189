"""The part of `collective_share` in which the same device plane's `XLA Ops`
line ran nothing else: what the collectives cost the step. A synchronous
collective is exposed for its whole length; of a split one, the stretches
between its start and its done that no other operation covers."""

DECLARATION = {"name": "collective_exposed_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "gradient all-reduce (data axis)", "moves": "step_ms"}


def read(ctx):
    t = ctx["trace"]
    spent = t.collective_seconds if t is not None else None
    if spent is None:
        return None
    return 100.0 * spent[1] / t.window_s
