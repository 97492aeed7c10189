"""The held experts' grouped products' share of their roofline: the least time
a chip could take for their required work in the traced steps (the larger of
required FLOPs / peak FLOP/s and required bytes / peak bytes/s; the
configuration's FLOPs module reckons both in ``moe_experts_required_per_step``
from shapes and from the pairs the routers really sent to the experts held
here, whatever implements the products) over the device time under the
`moe_experts` scope. The pairs are the program's `moe.pairs_local` counter a
step of the window (the traced stretch is the epoch before it, on the same
weights but two updates: the routing a seed gives moves by tenths of a percent
an epoch); a program that counts none gives nothing. FLOPs bind from about 350
pairs an expert a layer (60 MFLOP and 21 KB a pair, beside 640 MB a layer for
the weights and their gradients); under that the bytes do. Recomputation is
in the time and not in the work."""

import importlib

from benchmarks.lib import spans

DECLARATION = {"name": "moe_experts_roofline", "unit": "%", "better": "higher", "source": "device_trace",
               "layer": "grouped expert matmul", "moves": "step_ms"}


def read(ctx):
    if ctx["peaks"] is None:
        return None
    reckon = getattr(importlib.import_module(ctx["cfg"]["flops"]), "moe_experts_required_per_step", None)
    share = spans.scope_share(ctx, ("moe_experts",))
    if reckon is None or not share:
        return None
    view = spans.load(ctx)
    if view is None or not view.counters.get("moe.pairs_local") or not view.steps:
        return None
    need, peaks = reckon(ctx["cfg"], ctx["traffic"], view.counters["moe.pairs_local"] / view.steps), ctx["peaks"]
    least = max(need["flops"] / peaks["flops_per_s_bf16"], need["bytes"] / peaks["hbm_bytes_per_s"])
    spent = share / 100.0 * ctx["trace"].window_s
    return 100.0 * least * ctx["trace_steps"] / ctx["chips"] / spent
