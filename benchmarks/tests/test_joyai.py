"""The ``joyai_llm_flash`` configuration's files: its trainer glue and plain
reference through the harness at a tiny float32 size on the CPU
(``data_joyai/``: a benchmark file, a configuration and a traffic mix of its
own, so that no file the benchmark has is edited), its FLOPs module held to
numbers worked by hand here, the cell as the repo's BENCHMARK.json declares it
after the accepted entries, and the three per-layer readers on a toy trace."""

import importlib.util
import json
import math
import os

import pytest

from benchmarks.flops import joyai_llm_flash as flops
from benchmarks.lib import cells, harness, trace
from benchmarks.reference import joyai_llm_flash as ref

DATA = os.path.join(os.path.dirname(__file__), "data_joyai")
BENCH = os.path.join(DATA, "BENCHMARK.json")
CELL, CONFIG = "joyaiflash_t8192", "joyai-llm-flash"
NEW_METRICS = ["mla_time_share", "mla_flash_roofline", "mtp_time_share"]
DEV = "/device:TPU:0"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
# the published config.json's keys that say something about the model's shape, as its source gives them
PUBLISHED = {
    'attention_bias': False, 'ep_size': 1, 'first_k_dense_replace': 1, 'head_dim': 64, 'hidden_act': 'silu',
    'hidden_size': 2048, 'intermediate_size': 7168, 'kv_lora_rank': 512, 'max_position_embeddings': 131072,
    'model_type': 'joyai_llm_flash', 'moe_intermediate_size': 768, 'moe_layer_freq': 1, 'n_group': 1,
    'n_routed_experts': 256, 'n_shared_experts': 1, 'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_experts_per_tok': 8, 'num_hidden_layers': 40, 'num_key_value_heads': 32, 'num_nextn_predict_layers': 1,
    'q_lora_rank': 1536, 'qk_head_dim': 192, 'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_interleave': True, 'rope_scaling': None, 'rope_theta': 32000000, 'routed_scaling_factor': 2.5,
    'scoring_func': 'sigmoid', 'tie_word_embeddings': False, 'topk_group': 1, 'topk_method': 'noaux_tc',
    'v_head_dim': 128, 'vocab_size': 129280
}


@pytest.fixture(autouse=True)
def no_recorder_left_behind():
    yield
    from distributed_training_pytorch_tpu import profiling

    profiling.uninstall_recorder()


def run(stand_in=None, trace=False, seed=2**31 + 11):
    return harness.run_cell("joyai_tiny", seed, 0.5, trace, require_tpu=False, bench_file=BENCH, data_dirs=[DATA],
                            stand_in=stand_in)


def tiny():
    return cells.load_cell("joyai_tiny", BENCH, [DATA])


def real():
    return cells.load_cell(CELL)


def bench_file():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- through the harness ------------------------------------------------------


def test_the_tiny_stack_is_correct_through_the_harness():
    """Float32 program against the float32 reference through ``Trainer``:
    round-off, far under limits that the control and the planted fault are
    far over. A CPU trace has no device plane: nothing is reported under a
    device metric's name."""
    line = run(trace=True)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert all(c["value"] < 1e-5 for c in line["checks"].values()), line["checks"]
    assert not set(NEW_METRICS) & set(line["metrics"])
    assert line["info"]["recorded_steps"] == 2 and line["info"]["problems"] == []
    assert any(".e_score_correction_bias" in leaf for leaf in line["info"]["leaves_left_out"])


@pytest.mark.parametrize("stand_in,over", [("control", ["grad_gap", "delta_gap"]), ("half_batch", ["loss_gap", "grad_gap", "delta_gap"])])
def test_the_control_and_the_planted_fault_are_not_correct(stand_in, over):
    """bfloat16 operands under the stated float32, and half of the rows left out, in the program's place."""
    line = run(stand_in=stand_in)
    assert line["correct"] is False
    for key in over:
        assert line["checks"][key]["value"] > line["checks"][key]["limit"], (key, line["checks"][key])


def test_the_reference_and_the_program_agree_on_the_tree():
    import jax
    import jax.numpy as jnp

    cfg = tiny().config
    params = ref.init_params(cfg, {}, jax.random.key(0))
    assert {k: v.shape for k, v in params.items()} == ref.param_shapes(cfg, {})
    back = ref.from_program(ref.to_program(params, cfg), cfg)
    assert set(back) == set(params) and all(back[k] is params[k] for k in params)
    split = ref.leaves(params, cfg)
    assert split["layers.0.mlp.gate_proj.w"].shape == split["layers.0.mlp.up_proj.w"].shape == (64, 128)
    assert split["layers.1.mlp.experts.gate_proj.w"].shape == (4, 64, 32) and split["mtp.block.mlp.experts.down_proj.w"].shape == (4, 32, 64)
    assert split["layers.2.self_attn.kv_a_proj_with_mqa.latent.w"].shape == (64, 32)
    assert split["layers.2.self_attn.kv_a_proj_with_mqa.rope.w"].shape == (64, 8)
    total = sum(float(jnp.sum(jnp.square(v))) for v in split.values())
    assert total == pytest.approx(sum(float(jnp.sum(jnp.square(v))) for v in params.values()), rel=1e-6)
    assert float(jnp.abs(params["layers.1.mlp.gate.e_score_correction_bias"]).max()) == 0  # as assumed
    assert params["lm_head.w"].shape == params["embeddings"].shape and not bool(jnp.all(params["lm_head.w"] == params["embeddings"]))


# -- the FLOPs module ---------------------------------------------------------


def test_the_tiny_presets_work_is_hand_counted():
    cell = tiny()
    cfg, traffic = cell.config, cell.traffic
    # attention: q_a 64 x 48, q_b 48 x 4 x 24, kv_a 64 x 40, kv_b 32 x 4 x 32, o 64 x 64; norms 48 + 32 + 2 x 64
    mla, norms = 3_072 + 4_608 + 2_560 + 4_096 + 4_096, 48 + 32 + 128
    dense, expert, router = 3 * 64 * 128, 3 * 64 * 32, 8 * 64  # 4 of 8 held, top-3: 1.5 pairs a token here
    moe = router + expert + 1.5 * expert
    matmul = 3 * mla + dense + 2 * moe + (2 * 64 * 64 + mla + moe) + 2 * 97 * 64
    held_moe = router + 8 + expert + 4 * expert
    held = 3 * (mla + norms) + dense + 2 * held_moe + (2 * 64 * 64 + 3 * 64 + mla + norms + held_moe) + 2 * 97 * 64 + 64
    assert flops.local_pairs_per_token(cfg) == 1.5
    assert flops.parameter_count(cfg) == {"matmul": matmul, "all": held}
    assert held == sum(math.prod(s) for s in ref.param_shapes(cfg, traffic).values())
    attention = 3 * 4 * 64 * 4 * (24 + 16)  # 4 layers with the module's, T = 64
    assert flops.attention_flops_per_token(cfg, 64) == attention
    assert flops.required_flops_per_step(cfg, traffic) == (6 * matmul + attention) * 64 * 8
    need = flops.flash_required_per_step(cfg, traffic)
    assert need == {"flops": attention * 64 * 8, "bytes": 4 * (6 * 24 + 6 * 16) * 2 * 4 * 64 * 8}


def test_the_real_files_work():
    cell = real()
    cfg, traffic = cell.config, cell.traffic
    count = flops.parameter_count(cfg)
    part = cfg["parameters"]["by_part"]
    assert count["all"] == 680_441_088 == cfg["parameters"]["count"]
    assert count["all"] == sum(math.prod(s) for s in ref.param_shapes(cfg, traffic).values())
    assert count["all"] == (part["dense_layer"] + 4 * part["expert_layer_16_held"] + part["mtp_module"]
                            + part["embedding_slice"] + part["head_slice"] + part["final_norm"])
    assert part["expert_layer_16_held"] == part["mla_attention"] + 2 * 2048 + 16 * part["one_routed_expert"] \
        + part["shared_expert"] + part["router_with_bias"]
    assert part["mtp_module"] == part["expert_layer_16_held"] + 2048 * 4096 + 3 * 2048
    assert part["mla_attention"] == 2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256 + 32 * 128 * 2048
    assert round(cfg["parameters"]["training_state_gb"], 2) == round(16 * count["all"] / 1e9, 2)
    assert flops.local_pairs_per_token(cfg) == 0.5  # 8 x 16 / 256
    assert count["matmul"] == 314_703_872
    step = flops.required_flops_per_step(cfg, traffic)
    assert 55.6e12 < step < 55.7e12 and step / 197e12 == pytest.approx(0.2826, abs=1e-4)  # 283 ms at peak
    need = flops.flash_required_per_step(cfg, traffic)
    assert need["flops"] == 3 * 6 * 8192 * 32 * 320 * 16_384 and need["flops"] / step == pytest.approx(0.444, abs=1e-3)
    assert need["bytes"] == 32 * (6 * 192 + 6 * 128) * 2 * 6 * 16_384
    assert need["flops"] / 197e12 > 8 * need["bytes"] / 819e9  # FLOPs bind, 126 ms against 15


# -- the entries --------------------------------------------------------------


def test_the_real_configuration_is_the_published_one_cut_as_it_says():
    cfg = real().config
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    published = PUBLISHED
    assert cfg["reduced"] == reduced and cfg["model_type"] == "joyai_llm_flash" and cfg["family"] == "joyai_llm_flash"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"], cfg["experts_held_first"]) == (5, 16, 16_160, 0)
    widths = {"hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 768, "num_attention_heads": 32,
              "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
              "v_head_dim": 128, "num_experts_per_tok": 8, "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
              "rope_theta": 32_000_000, "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["source"] == "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json"
    # every key of the published config.json is in the file, changed only where `reduced` says
    assert {k: cfg[k] for k in published if k not in reduced} == {k: v for k, v in published.items() if k not in reduced}
    assert {k: published[k] for k in reduced} == cfg["published"]
    assert "16 chips" in cfg["deployment"] and "over 8" in cfg["deployment"]
    assert cfg["assumed"]["mtp_loss_weight"] == 0.3 and {"e_score_correction_bias", "mtp_concat_order", "precision"} <= set(cfg["assumed"])
    assert cfg["memory"] == {"remat": "block"} and cfg["precision"]["control"] == "fp8"
    for key in ("system", "reference", "flops"):
        assert importlib.util.find_spec(cfg[key]) is not None, cfg[key]


def test_the_cell_loads_from_the_repos_benchmark_file():
    cell = real()
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    want = {"seq_len": 8192, "global_batch": 2, "chain_steps": 2, "steps_per_epoch": 8, "log_every": 50, "mesh": {"data": 1},
            "chips": 1, "check_steps": 2, "reference_block_rows": 1, "trace_slices": 1}
    assert {k: cell.traffic[k] for k in want} == want
    assert set(cell.traffic["limits"]) == {CONFIG} and set(cell.traffic["limits"][CONFIG]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert [m["name"] for m in cell.end_to_end] == ["step_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_METRICS) | {"step_mfu", "peak_hbm_gib", "device_idle_share", "program_load_s"} <= set(names)
    row = next(w for w in bench_file()["workloads"] if w["name"] == CELL)
    assert len(row["why"]) <= 200 and "16x" in row["why"] and "44%" in row["why"]


def test_the_accepted_entries_stand_as_they_were_before_the_new_ones():
    """By name and in order, each list: the parent's entries first, this
    cell's after them (what a later PR appends comes after these)."""
    bench = bench_file()
    configs = ["gpt2-small", "vgg16-cifar10", "granite-4.0-h-micro", "nemotron-3-nano-30b-a3b"]
    workloads = ["gpt2s_t1024", "gpt2s_t4096", "vgg16_cifar_b4096", "gpt2s_t1024_dp4", "granite4h_t4096", "gpt2s_t8192",
                 "nemotron3nano_t8192"]
    assert [c["name"] for c in bench["configs"]][:5] == configs + [CONFIG]
    assert [w["name"] for w in bench["workloads"]][:8] == workloads + [CELL]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index("mamba_conv_time_share") + 1:][:3] == NEW_METRICS
    assert [m["name"] for m in bench["end_to_end"]] == ["step_ms", "setup_s"] and bench["run_seconds"] == 10


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(cells.BENCH_DIR, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metrics_file_declares_what_its_entry_says(name):
    bench = bench_file()
    assert [m["name"] for m in bench["per_layer"]].count(name) == 1
    entry = dict(next(m for m in bench["per_layer"] if m["name"] == name))
    assert entry.pop("workloads") == [CELL]
    assert reader(name).DECLARATION == entry and entry["moves"] == "step_ms" and entry["unit"] == "%"
    assert entry["source"] == "device_trace"


# -- the readers on a toy trace ------------------------------------------------


def toy_ctx(ops, cell, peaks=PEAKS):
    """``ops``: (label, start ns, ns). A traced stretch of 10 µs on one device plane."""
    summary = trace.summarize([(DEV, [("XLA Ops", [(label, label + " 1.0", s, d) for label, s, d in ops])])], 1e-5)
    return {"trace": summary, "cfg": cell.config, "traffic": cell.traffic, "peaks": peaks, "chips": 1, "steps": 8, "trace_steps": 8}


def test_the_three_readers_on_a_toy_trace():
    """The latent layer's time is everything under `mla_attention` and the
    flash backward by its own name; the roofline's time is the two Mosaic
    flash calls' alone; the module's is everything under `mtp`."""
    cell = real()
    fwd = "jit(chained)/jvp(HybridLM)/layer_1/self_attn/mla_attention/"
    mtp = "jit(chained)/jvp(HybridLM)/mtp/mtp/block/"
    ops = [(f"%fusion.1 = fusion() {fwd}mla_q/q_a_proj/dot_general", 0, 500),
           (f"%flash_fwd.1 = custom-call() custom_call_target=\"tpu_custom_call\" {fwd}jit(_fwd_call)/flash_fwd", 500, 1500),
           ("%flash_dqkv.2 = custom-call() custom_call_target=\"tpu_custom_call\" jit(chained)/transpose(jvp(HybridLM))/flash_dqkv", 2000, 2000),
           ("%moe_rows.1 = custom-call() custom_call_target=\"tpu_custom_call\" jit(chained)/layer_1/mlp/moe_layer/moe_dispatch", 4000, 1000),
           (f"%fusion.2 = fusion() {mtp}self_attn/mla_attention/mla_kv/dot_general", 5000, 1000),
           (f"%fusion.3 = fusion() {mtp}mlp/moe_layer/moe_experts/ragged_dot", 6000, 1000),
           ("%fusion.4 = fusion() jit(chained)/jvp(mtp_loss_head)/dot_general", 7000, 1000)]
    ctx = toy_ctx(ops, cell)
    assert reader("mla_time_share").read(ctx) == pytest.approx(50.0)
    assert reader("mtp_time_share").read(ctx) == pytest.approx(30.0)
    need = flops.flash_required_per_step(cell.config, cell.traffic)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9) * 8
    assert reader("mla_flash_roofline").read(ctx) == pytest.approx(100.0 * least / 3.5e-6)
    assert reader("mla_flash_roofline").read(dict(ctx, peaks=None)) is None  # off the chip nothing needs a peak


def test_the_readers_return_nothing_where_the_program_lacks_the_scopes():
    """As in a cell of another family: no latent layer, no module, no flash call."""
    other = [("%fusion.1 = fusion() jit(chained)/jvp(HybridLM)/layer_0/mamba/mamba_mixer/ssd_scan/dot_general", 0, 1000)]
    for name in NEW_METRICS:
        assert reader(name).read(toy_ctx(other, real())) is None, name
        assert reader(name).read({"trace": None, "peaks": PEAKS, "cfg": real().config, "traffic": {}}) is None, name
    flash = [("%flash_fwd.1 = custom-call() custom_call_target=\"tpu_custom_call\" jit(chained)/flash_fwd", 0, 1000)]
    assert reader("mla_flash_roofline").read(toy_ctx(flash, cells.load_cell("gpt2s_t8192"))) is None  # its FLOPs module reckons none
