"""Device idle time of the traced stretch that falls while the main thread is
in a `trainer.fetch` span (waiting for the next unit from the prefetch ring),
over the stretch. Each idle gap is split by length of overlap; the three
`idle_in_*` shares sum to `device_idle_share` (benchmarks/lib/spans.py)."""

from benchmarks.lib import spans

DECLARATION = {"name": "idle_in_fetch_share", "unit": "%", "better": "lower", "source": "program_span",
               "layer": "data loader and prefetch", "moves": "step_ms"}


def read(ctx):
    return spans.idle_share(ctx, "fetch")
