"""The whole step's share of the chips' published bf16 peak: required FLOPs
per step (benchmarks/lib/flops.py: no recomputation, no masked half) x steps
in the window / window wall / (peak x chips). The time is the host's clock
over the whole window, between two device syncs."""

from benchmarks.lib import flops

DECLARATION = {"name": "step_mfu", "unit": "%", "better": "higher", "source": "host_clock",
               "layer": "train engine whole step", "moves": "step_ms"}


def read(ctx):
    if ctx["peaks"] is None:
        return None
    if not ctx["steps"]:
        return None
    need = flops.required_flops_per_step(ctx["cfg"], ctx["traffic"]) * ctx["steps"]
    return 100.0 * need / ctx["window_s"] / (ctx["peaks"]["flops_per_s_bf16"] * ctx["chips"])
