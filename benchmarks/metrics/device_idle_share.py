"""1 - (union of the device's operation intervals / traced window), averaged
over the chips used. The traced window is whole slices of the loop between
two device syncs, so epoch glue and loader restarts are in it."""

DECLARATION = {"name": "device_idle_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "device", "moves": "step_ms"}


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
