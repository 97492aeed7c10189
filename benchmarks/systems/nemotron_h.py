"""``examples/train_lm.py``'s ``LMTrainer`` around ``models/hybrid_lm.py``'s
stack built from a ``nemotron_h`` configuration file: ``systems/hybrid_lm.py``'s
trainer, with this family's published keys mapped onto the values the stack
is built from (``hybrid_config``), and what the cell's `why` promises about
the path taken: flash attention and the scan's Pallas kernels. On a commit
without the expert layer the first import below fails and ``run.py`` exits 2
with no result line."""

from __future__ import annotations

from distributed_training_pytorch_tpu.parallel.moe import HeldExpertsMlp  # noqa: F401  (the layer the family needs)

from benchmarks.systems.common import trainer_kwargs  # noqa: E402
from benchmarks.systems.hybrid_lm import BenchHybridTrainer, prepare  # noqa: F401, E402
from distributed_training_pytorch_tpu.models.hybrid_lm import ATTENTION, MAMBA, MOE, HybridConfig  # noqa: E402

MODEL = "nemotron_h"  # whose kernel_dispatch records these are
KINDS = {"M": MAMBA, "*": ATTENTION, "E": MOE}  # hybrid_override_pattern ("-", a plain MLP layer: not built)
WANTED = {"use_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
          "use_bias": False, "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2", "norm_topk_prob": True,
          "n_group": 1, "topk_group": 1, "n_shared_experts": 1, "tie_word_embeddings": False}


def hybrid_config(cfg: dict) -> HybridConfig:
    """The published ``nemotron_h`` keys as the stack's values; what the stack
    cannot do is refused. ``n_routed_experts`` is the count held here where
    the file cuts it (``published.n_routed_experts`` then says how many the
    router scores, ``experts_held_first`` which is the first held)."""
    for key, value in WANTED.items():
        if cfg.get(key, value) != value:
            raise NotImplementedError(f"nemotron_h: {key}={cfg[key]!r} is not supported (only {value!r})")
    unknown = set(cfg["hybrid_override_pattern"]) - set(KINDS)
    if unknown:
        raise NotImplementedError(f"nemotron_h: layer kinds {sorted(unknown)} of hybrid_override_pattern are not supported")
    held = cfg["n_routed_experts"]
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(KINDS[kind] for kind in cfg["hybrid_override_pattern"]),
        num_attention_heads=cfg["num_attention_heads"], num_key_value_heads=cfg["num_key_value_heads"],
        attention_head_dim=cfg["head_dim"], mamba_n_heads=cfg["mamba_num_heads"], mamba_d_head=cfg["mamba_head_dim"],
        mamba_d_state=cfg["ssm_state_size"], mamba_n_groups=cfg["n_groups"], mamba_d_conv=cfg["conv_kernel"],
        mamba_chunk_size=cfg["chunk_size"], rms_norm_eps=cfg["layer_norm_epsilon"], tie_word_embeddings=False,
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        n_routed_experts=cfg.get("published", {}).get("n_routed_experts", held),
        experts_held=(cfg.get("experts_held_first", 0), held), num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"], dispatch_name=MODEL,
    )


def build(cfg: dict, traffic: dict, data: dict, **common):
    remat = cfg["memory"]["remat"]
    if remat not in ("block", "none"):
        raise ValueError(f"memory.remat {remat!r}: want 'block' or 'none'")
    dims = {"cfg": hybrid_config(cfg), "remat": remat == "block"}
    return BenchHybridTrainer(data["windows"], dims, traffic["seq_len"], cfg["optimizer"]["lr"],
                              **trainer_kwargs(cfg, traffic, **common))


def expect_kernels(cfg: dict, on_tpu: bool) -> list[str]:
    """Failures of what the cell's `why` promises about the path taken."""
    from distributed_training_pytorch_tpu.ops import dispatch

    if not on_tpu:  # off the chip (tests) auto resolves to plain / chunked, or PALLAS=1 forces the interpreted kernels
        return []
    problems = []
    for op, want in (("attention", "flash"), ("ssd", "pallas")):
        recs = [r for r in dispatch.records() if r["model"] == MODEL and r["op"] == op]
        if not recs or any(r["path"] != want for r in recs):
            problems.append(f"{op} did not resolve to {want}: {recs}")
    return problems
