"""Device idle time of the traced stretch that falls while the main thread is
anywhere but a fetch or a dispatch: `trainer.epoch_start`, `trainer.sync`,
`trainer.epoch_end`, the rest of `trainer.epoch` / `trainer.train`, and the
harness between two slices. See `idle_in_fetch_share`."""

from benchmarks.lib import spans

DECLARATION = {"name": "idle_in_glue_share", "unit": "%", "better": "lower", "source": "program_span",
               "layer": "trainer epoch loop", "moves": "step_ms"}


def read(ctx):
    return spans.idle_share(ctx, "glue")
