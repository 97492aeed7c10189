"""Device time of the Mamba-2 mixer's causal convolution with its SiLU over the
traced stretch: every operation whose scope holds `mamba_conv`
(``models/hybrid_lm.py:MambaMixer`` wraps the call in it, and since PR 39 each
of ``ops/causal_conv.py``'s two kernels, `conv_fwd` and `conv_bwd`, sits in a
`mamba_conv` scope of its own, which is what keeps the ``custom_vjp``'s
backward half under it), so the forward pass, its recomputation in the
rematerialised block and the backward pass together. The parent's
``jax.numpy`` form carries the same scope."""

from benchmarks.lib import spans

DECLARATION = {"name": "mamba_conv_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "mamba-2 causal convolution", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("mamba_conv",))
