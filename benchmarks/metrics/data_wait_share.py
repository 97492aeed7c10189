"""Share of the window's wall time in the goodput meter's `data_wait`: the
loop's wait on the next unit from the loader / prefetch ring. By the meter's
own docstring device back-pressure surfaces here too, so read it as an upper
bound on input-boundness."""

DECLARATION = {"name": "data_wait_share", "unit": "%", "better": "lower", "source": "program_span",
               "layer": "data loader and prefetch", "moves": "step_ms"}


def read(ctx):
    g = ctx["goodput"]
    if not g or "data_wait" not in g:
        return None
    return 100.0 * g["data_wait"] / ctx["window_s"]
