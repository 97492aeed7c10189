"""Device time of the state-space scan over the traced stretch: every
operation whose scope holds `ssd_scan` (``models/hybrid_lm.py`` wraps
``ops/ssd.py:ssd_chunked`` in it), so the forward pass, its recomputation in
the rematerialised block and the backward pass together."""

from benchmarks.lib import spans

DECLARATION = {"name": "ssd_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "mamba-2 state-space scan", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("ssd_scan",))
