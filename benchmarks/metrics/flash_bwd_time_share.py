"""Device time of flash attention's one backward kernel (`flash_dqkv`: the
`name=` of its `pl.pallas_call`, which names the instruction, and the scope
around it) over the traced stretch; `flash_time_share` less this is the
forward kernel's."""

from benchmarks.lib import spans

DECLARATION = {"name": "flash_bwd_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "pallas flash attention", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("flash_dqkv",))
