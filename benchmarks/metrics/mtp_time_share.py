"""Device time of the multi-token-prediction module over the traced stretch:
every operation whose scope holds `mtp` (``models/hybrid_lm.py:
MultiTokenPrediction``: its two norms, the joined projection `eh_proj`, its
latent-attention and expert block, its final norm; and its pass through the
fused head, which ``make_fused_lm_loss`` runs under `mtp_loss_head`),
forward, recomputed and backward. The backward halves of ``custom_vjp``s
(the flash backward, the expert layer's row movements, the head's scaling)
name their own scopes and are not in it."""

from benchmarks.lib import spans

DECLARATION = {"name": "mtp_time_share", "unit": "%", "better": "lower", "source": "device_trace",
               "layer": "multi-token prediction module", "moves": "step_ms"}


def read(ctx):
    return spans.scope_share(ctx, ("mtp",))
