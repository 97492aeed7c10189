"""Summed time of the `loader.batch` spans that started inside the window,
all workers together, over the window's steps: the host work of producing
one step's batch (native crop/flip, or decode and collate)."""

from benchmarks.lib import spans

DECLARATION = {"name": "produce_ms_per_step", "unit": "ms", "better": "lower", "source": "program_span",
               "layer": "data loader and prefetch", "moves": "step_ms"}


def read(ctx):
    view = spans.load(ctx)
    if view is None or not view.steps:
        return None
    made = view.in_window(spans.PRODUCE)
    if not made:
        return None
    return sum(s[2] - s[1] for s in made) / 1e6 / view.steps
