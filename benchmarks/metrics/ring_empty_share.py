"""Of the times the loop asked the prefetch ring for a unit inside the window
(`prefetch.fetches`), the share that found it empty (`prefetch.fetches_empty`):
counted on the consumer's side just before it blocks."""

from benchmarks.lib import spans

DECLARATION = {"name": "ring_empty_share", "unit": "%", "better": "lower", "source": "program_counter",
               "layer": "data loader and prefetch", "moves": "step_ms"}


def read(ctx):
    view = spans.load(ctx)
    if view is None or not view.counters.get("prefetch.fetches"):
        return None
    return 100.0 * view.counters.get("prefetch.fetches_empty", 0) / view.counters["prefetch.fetches"]
