"""Device time of the operations under the `loss_head` scope
(`ops/losses.py:tied_cross_entropy_loss`: the forward scan, which takes the
loss and both gradients, and the backward's scaling carry it) over the traced
stretch. The scope is the `tf_op` stat of the event's metadata in the xplane
(benchmarks/lib/xplane.py); containers are left out, overlaps counted once."""

from benchmarks.lib import spans

DECLARATION = {"name": "loss_head_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "fused tied cross-entropy head", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("loss_head",))
