"""The state-space scan's share of its roofline: the least time a chip could
take for the scan's required work in the traced steps (the larger of required
FLOPs / peak FLOP/s and required bytes / peak bytes/s; the configuration's
FLOPs module reckons both from shapes in ``ssd_required_per_step``, whatever
implements the scan) over the device time under the `ssd_scan` scope. By that
count the scan is bound by memory (17 KB a token a layer forward against
2.6 MFLOP), so this is a share of the bandwidth roofline; recomputation is
in the time and not in the work."""

import importlib

from benchmarks.lib import spans

DECLARATION = {"name": "ssd_roofline", "unit": "%", "better": "higher", "source": "device_trace",
               "layer": "mamba-2 state-space scan", "moves": "step_ms"}


def read(ctx):
    if ctx["peaks"] is None:
        return None
    reckon = getattr(importlib.import_module(ctx["cfg"]["flops"]), "ssd_required_per_step", None)
    share = spans.scope_share(ctx, ("ssd_scan",))
    if reckon is None or not share:
        return None
    need, peaks = reckon(ctx["cfg"], ctx["traffic"]), ctx["peaks"]
    least = max(need["flops"] / peaks["flops_per_s_bf16"], need["bytes"] / peaks["hbm_bytes_per_s"])
    spent = share / 100.0 * ctx["trace"].window_s
    return 100.0 * least * ctx["trace_steps"] / ctx["chips"] / spent
