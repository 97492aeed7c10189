"""The latent-attention flash calls' share of their roofline: the least time a
chip could take for their required work in the traced steps (the larger of
required FLOPs / peak FLOP/s and required bytes / peak bytes/s; the
configuration's FLOPs module reckons both in ``flash_required_per_step``, the
function `flash_roofline` reads too, from shapes, queries and keys at
``qk_head_dim`` and values at ``v_head_dim``, whatever implements the calls)
over the summed device time of the Mosaic flash calls, forward (`flash_fwd`)
and backward (`flash_dqkv`), found by the kernels' names: in a configuration
whose every attention layer is latent every flash call is one of these, and
`flash_roofline`, which sums every Mosaic call of the trace, would count any
other Mosaic call with them. FLOPs bind, by about 8.5 to 1 at T = 8192.
Recomputation is in the time and not in the work, so the share cannot pass
100%. Nothing is read for a configuration without latent attention."""

from benchmarks.lib import flops, spans
from benchmarks.lib.trace import MOSAIC_CALL

DECLARATION = {"name": "mla_flash_roofline", "unit": "%", "better": "higher", "source": "device_trace",
               "layer": "pallas flash attention", "moves": "step_ms"}
KERNELS = ("flash_fwd", "flash_dqkv")


def read(ctx):
    trace = ctx.get("trace")
    if ctx.get("peaks") is None or trace is None or not trace.devices or "kv_lora_rank" not in ctx["cfg"]:
        return None
    need = flops.flash_required_per_step(ctx["cfg"], ctx["traffic"])
    if need is None:
        return None
    found = spans.session(ctx)
    scopes = found.scopes if found is not None else {}
    per = [sum(o.dur_ns for o in ops if any(p in o.label for p in MOSAIC_CALL)
               and any(k in o.label or k in scopes.get(o.name, "") for k in KERNELS)) for ops in trace.devices.values()]
    spent = sum(per) / len(per) / 1e9
    if not spent:
        return None
    peaks = ctx["peaks"]
    least = max(need["flops"] / peaks["flops_per_s_bf16"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["trace_steps"] / ctx["chips"] / spent
