"""Device time of the latent-attention layers over the traced stretch: every
operation whose scope holds `mla_attention` (``models/hybrid_lm.py:
LatentAttention``: the query and key/value projections with their norms
(`mla_q`, `mla_kv`), the rotary (`mla_rope`), the flash forward, the output
projection), forward, recomputed and backward, and the flash backward kernel
`flash_dqkv` by its own name: a ``custom_vjp``'s backward half does not carry
the scopes of its call site, and in a configuration whose every attention
layer is latent every flash call is one of these. The prediction module's
layer counts too."""

from benchmarks.lib import spans

DECLARATION = {"name": "mla_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "latent attention", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("mla_attention", "flash_dqkv"))
