"""Device time of the forward pass computed a second time, over the traced
stretch: what rematerialising the blocks costs. ``jax.checkpoint`` (flax's
``nn.remat`` is the same transformation) puts `rematted_computation` into the
name of every operation it recomputes in the backward pass, and the name
reaches the trace as the scope of the event's metadata. Seen on the chip
(PR 34): ``jit(chained)/closed_call/transpose(jvp(HybridLM))/jvp(HybridLM)/
checkpoint/rematted_computation/layer_0/mamba/mamba_mixer/in_proj/dot_general``
for the second forward pass, the same path without ``rematted_computation/``
for the backward pass proper, and ``…/jvp(HybridLM)/layer_0/…`` for the
first."""

from benchmarks.lib import spans

DECLARATION = {"name": "remat_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "hybrid decoder block", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("rematted_computation",))
