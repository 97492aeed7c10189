"""Device time of flash attention's two backward kernels (`flash_dq`,
`flash_dkv`: the `name=` of their `pl.pallas_call`s, which names the
instruction, and the scope around them) over the traced stretch;
`flash_time_share` less this is the forward kernel's."""

from benchmarks.lib import spans

DECLARATION = {"name": "flash_bwd_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "pallas flash attention", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("flash_dq", "flash_dkv"))
