"""Device time the routed expert layers spend on not multiplying, over the
traced stretch: the operations under `moe_router` (scores), `moe_dispatch`
(top-k, the counting sort, the gather into the pairs' buffer) and
`moe_combine` (weights, the gather back and the sum over a token's pairs),
forward, recomputed and backward. They run over every (token, choice) pair,
held here or not."""

from benchmarks.lib import spans

DECLARATION = {"name": "moe_dispatch_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "routed expert layer", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("moe_router", "moe_dispatch", "moe_combine"))
