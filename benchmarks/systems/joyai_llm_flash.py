"""``examples/train_lm.py``'s ``LMTrainer`` around ``models/hybrid_lm.py``'s
stack built from a ``joyai_llm_flash`` configuration file (DeepSeek-V3's
block): ``systems/hybrid_lm.py``'s trainer, with this family's published keys
mapped onto the values the stack is built from (``hybrid_config``), and what
the cell's `why` promises about the path taken: flash attention at the latent
attention's own two widths, the experts' grouped products. On a commit
without latent attention the first import below fails and ``run.py`` exits 2
with no result line."""

from __future__ import annotations

from distributed_training_pytorch_tpu.models.hybrid_lm import LatentAttention  # noqa: F401  (the layer the family needs)

from benchmarks.systems.common import trainer_kwargs  # noqa: E402
from benchmarks.systems.hybrid_lm import BenchHybridTrainer, prepare  # noqa: F401, E402
from distributed_training_pytorch_tpu.models.hybrid_lm import DENSE, MLA, MOE, HybridConfig  # noqa: E402

MODEL = "joyai_llm_flash"  # whose kernel_dispatch records these are
WANTED = {"attention_bias": False, "hidden_act": "silu", "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True, "rope_scaling": None, "rope_interleave": True,
          "tie_word_embeddings": False, "n_shared_experts": 1, "moe_layer_freq": 1}


def hybrid_config(cfg: dict) -> HybridConfig:
    """The published ``joyai_llm_flash`` keys as the stack's values; what the
    stack cannot do is refused. ``n_routed_experts`` is the count held here
    where the file cuts it (``published.n_routed_experts`` then says how many
    the router scores, ``experts_held_first`` which is the first held)."""
    for key, value in WANTED.items():
        if cfg.get(key, value) != value:
            raise NotImplementedError(f"joyai_llm_flash: {key}={cfg[key]!r} is not supported (only {value!r})")
    if cfg["num_nextn_predict_layers"] > 1:
        raise NotImplementedError(f"joyai_llm_flash: num_nextn_predict_layers={cfg['num_nextn_predict_layers']} (at most 1)")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise NotImplementedError("joyai_llm_flash: latent attention has a key and a value for every head")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError(f"joyai_llm_flash: qk_head_dim {cfg['qk_head_dim']} is not nope + rope")
    layers, dense, held = cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["n_routed_experts"]
    mtp = cfg["num_nextn_predict_layers"]
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"], layer_types=(MLA,) * layers,
        num_attention_heads=cfg["num_attention_heads"], num_key_value_heads=cfg["num_key_value_heads"],
        mamba_n_heads=0, mamba_d_head=0, mamba_d_state=0, shared_intermediate_size=cfg["intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"], tie_word_embeddings=False,
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_intermediate_size=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        n_routed_experts=cfg.get("published", {}).get("n_routed_experts", held),
        experts_held=(cfg.get("experts_held_first", 0), held), num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"], moe_activation="swiglu",
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        ffn_types=tuple(DENSE if i < dense else MOE for i in range(layers)), mtp_layers=mtp,
        mtp_loss_weight=cfg["assumed"]["mtp_loss_weight"] if mtp else 0.0, dispatch_name=MODEL,
    )


def build(cfg: dict, traffic: dict, data: dict, **common):
    remat = cfg["memory"]["remat"]
    if remat not in ("block", "none"):
        raise ValueError(f"memory.remat {remat!r}: want 'block' or 'none'")
    dims = {"cfg": hybrid_config(cfg), "remat": remat == "block"}
    return BenchHybridTrainer(data["windows"], dims, traffic["seq_len"], cfg["optimizer"]["lr"],
                              **trainer_kwargs(cfg, traffic, **common))


def expect_kernels(cfg: dict, on_tpu: bool) -> list[str]:
    """Failures of what the cell's `why` promises about the path taken:
    attention on flash at query/key width ``qk_head_dim`` and value width
    ``v_head_dim`` (v not padded to q's), the experts on ``ragged_dot``."""
    from distributed_training_pytorch_tpu.ops import dispatch

    if not on_tpu:  # off the chip (tests) auto resolves to plain, or PALLAS=1 forces the interpreted kernel
        return []
    problems = []
    widths = (cfg["qk_head_dim"], cfg["v_head_dim"])
    recs = [r for r in dispatch.records() if r["model"] == MODEL and r["op"] == "attention"]
    if not recs or any(r["path"] != "flash" or (r.get("head_dim_qk"), r.get("head_dim_v")) != widths for r in recs):
        problems.append(f"attention did not resolve to flash at {widths[0]}/{widths[1]}: {recs}")
    recs = [r for r in dispatch.records() if r["model"] == MODEL and r["op"] == "moe_experts"]
    if not recs or any(r["path"] != "ragged_dot" for r in recs):
        problems.append(f"the experts did not resolve to ragged_dot: {recs}")
    return problems
