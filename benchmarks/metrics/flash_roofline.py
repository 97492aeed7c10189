"""Flash attention's share of its roofline: the least time a chip could take
for the causal forward + backward calls of the traced steps (the larger of
required FLOPs / peak FLOP/s and required bytes / peak bytes/s, per chip)
over the summed device time of the Mosaic flash calls in the trace.
At T = 1024 the two bounds are within 7% of each other (FLOPs bind, just);
at T = 4096 FLOPs bind by 4x."""

from benchmarks.lib import flops
from benchmarks.lib.trace import MOSAIC_CALL

DECLARATION = {"name": "flash_roofline", "unit": "%", "better": "higher", "source": "device_trace",
               "layer": "pallas flash attention", "moves": "step_ms"}


def read(ctx):
    # ops/pallas.py's flash_fwd and its one backward kernel flash_dqkv are the only Mosaic calls on the LM's path.
    if ctx["peaks"] is None:
        return None
    spent = ctx["trace"].op_seconds(MOSAIC_CALL)
    if not spent:
        return None
    need = flops.flash_required_per_step(ctx["cfg"], ctx["traffic"])
    if need is None:  # the configuration's FLOPs module reckons no flash call
        return None
    peaks = ctx["peaks"]
    least = max(need["flops"] / peaks["flops_per_s_bf16"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["trace_steps"] / ctx["chips"] / spent
