"""Summed `engine.dispatch` spans before the window whose call raised the
engine's `trace_counts` (`traced`): the seconds set-up spent tracing and
compiling the step programs, or loading them from the compile cache."""

from benchmarks.lib import spans

DECLARATION = {"name": "program_load_s", "unit": "s", "better": "lower", "source": "program_span",
               "layer": "train engine whole step", "moves": "setup_s"}


def read(ctx):
    view = spans.load(ctx)
    if view is None:
        return None
    before = [s for s in view.spans if s[0] == spans.DISPATCH and s[1] < view.window[0]]
    if not before:
        return None
    return sum(s[2] - s[1] for s in before if s[5].get("traced")) / 1e9
