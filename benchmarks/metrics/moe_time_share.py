"""Device time of the routed expert layers over the traced stretch: every
operation whose scope holds `moe_layer` (``parallel/moe.py:HeldExpertsMlp``:
router, top-k and sort, gather, the grouped products, combine, the shared
expert), forward, recomputed and backward."""

from benchmarks.lib import spans

DECLARATION = {"name": "moe_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "routed expert layer", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("moe_layer",))
