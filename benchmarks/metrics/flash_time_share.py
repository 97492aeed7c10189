"""Summed device time of the Mosaic flash calls over the traced stretch."""

from benchmarks.lib.trace import MOSAIC_CALL

DECLARATION = {"name": "flash_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "pallas flash attention", "moves": "step_ms"}


def read(ctx):
    spent = ctx["trace"].op_seconds(MOSAIC_CALL)
    if not spent:
        return None
    return 100.0 * spent / ctx["trace"].window_s
