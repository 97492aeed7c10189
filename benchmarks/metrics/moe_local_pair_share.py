"""Of the (token, choice) pairs the routers made inside the window, the share
that fell on the experts held here: the program's `moe.pairs_local` counter
(``LMTrainer`` adds each step's `moe_pairs_local` metric to it, which the
expert layers reckon from the routing the step itself did) over tokens x
experts per token x expert layers x steps. `held / published` (6.25% at 8 of
128) if routing is even, which is what ``moe_experts_roofline`` assumes; no
goal of its own."""

from benchmarks.lib import spans

DECLARATION = {"name": "moe_local_pair_share", "unit": "%", "better": "lower", "source": "program_counter",
               "layer": "routed expert layer", "moves": "step_ms"}


def read(ctx):
    view = spans.load(ctx)
    cfg, traffic = ctx["cfg"], ctx["traffic"]
    if view is None or not view.counters.get("moe.pairs_local") or not ctx["steps"]:
        return None
    layers = cfg["hybrid_override_pattern"].count("E")
    offered = traffic["seq_len"] * traffic["global_batch"] * cfg["num_experts_per_tok"] * layers * ctx["steps"]
    return 100.0 * view.counters["moe.pairs_local"] / offered
