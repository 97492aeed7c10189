"""Device idle time of the traced stretch that falls while the main thread is
in an `engine.dispatch` span (the call into the jitted step or chained
window), over the stretch. See `idle_in_fetch_share`."""

from benchmarks.lib import spans

DECLARATION = {"name": "idle_in_dispatch_share", "unit": "%", "better": "lower", "source": "program_span",
               "layer": "train engine whole step", "moves": "step_ms"}


def read(ctx):
    return spans.idle_share(ctx, "dispatch")
