"""Share of the window's wall time that the trainer's own goodput meter books
to anything but the step and the wait for input: epoch glue, logging, syncs
(`other`), and whatever else the loop did. Source: `telemetry/goodput.py`
buckets, read before and after the window (traced run: telemetry is on there
only)."""

DECLARATION = {"name": "loop_overhead_share", "unit": "%", "better": "lower", "source": "program_span",
               "layer": "trainer epoch loop", "moves": "step_ms"}


def read(ctx):
    g = ctx["goodput"]
    if not g:
        return None
    rest = sum(v for k, v in g.items() if k not in ("productive_step", "data_wait"))
    return 100.0 * rest / ctx["window_s"]
