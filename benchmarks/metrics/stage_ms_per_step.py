"""Self time of the `prefetch.stage` spans that started inside the window,
over the window's steps: what the `device-prefetch` thread spends stacking a
unit and handing it to the device (PJRT's host-side layout of the batch
included). Time blocked on a full ring is in no span. Against the device's
time a step it says whether that one thread keeps pace."""

from benchmarks.lib import spans

DECLARATION = {"name": "stage_ms_per_step", "unit": "ms", "better": "lower", "source": "program_span",
               "layer": "data loader and prefetch", "moves": "step_ms"}


def read(ctx):
    view = spans.load(ctx)
    if view is None or not view.steps:
        return None
    threads = {s[3] for s in view.in_window(spans.STAGE)}
    if not threads:
        return None
    lo, hi = view.window
    own = spans.self_ns([s for s in view.spans if s[3] in threads and lo <= s[1] <= hi])
    return own[spans.STAGE] / 1e6 / view.steps
