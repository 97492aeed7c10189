"""The ``nemotron_h`` configuration's files: its trainer glue and plain
reference through the harness at a tiny float32 size on the CPU
(``data_nemotron/``: a benchmark file, a configuration and a traffic mix of its
own, so that no file the benchmark has is edited), its FLOPs module held to
numbers worked by hand here, the cell of PR 36 as the repo's BENCHMARK.json
declares it after the accepted entries, and the four per-layer readers on a
toy trace."""

import importlib.util
import json
import math
import os

import pytest

from benchmarks.flops import nemotron_h as flops
from benchmarks.lib import cells, harness, trace
from benchmarks.reference import nemotron_h as ref

DATA = os.path.join(os.path.dirname(__file__), "data_nemotron")
BENCH = os.path.join(DATA, "BENCHMARK.json")
CELL, CONFIG = "nemotron3nano_t8192", "nemotron-3-nano-30b-a3b"
NEW_METRICS = ["moe_time_share", "moe_dispatch_time_share", "moe_experts_roofline", "moe_local_pair_share"]
DEV = "/device:TPU:0"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(autouse=True)
def no_recorder_left_behind():
    yield
    from distributed_training_pytorch_tpu import profiling

    profiling.uninstall_recorder()


def run(stand_in=None, trace=False, seed=2**31 + 9):
    return harness.run_cell("nemotron_tiny", seed, 0.5, trace, require_tpu=False, bench_file=BENCH, data_dirs=[DATA],
                            stand_in=stand_in)


def tiny():
    return cells.load_cell("nemotron_tiny", BENCH, [DATA])


def real():
    return cells.load_cell(CELL)


def bench_file():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- through the harness ------------------------------------------------------


def test_the_tiny_stack_is_correct_through_the_harness():
    """Float32 program against the float32 reference through ``Trainer``:
    round-off, far under limits that the control and the planted fault are far
    over. The counter metric is the program's own and is reported off the chip
    too: 4 of 8 experts are held, so about half of the pairs fall here."""
    line = run(trace=True)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert all(c["value"] < 1e-5 for c in line["checks"].values()), line["checks"]
    # a CPU trace has no device plane: nothing is reported under a device metric's name
    assert set(NEW_METRICS) & set(line["metrics"]) == {"moe_local_pair_share"}
    assert 30 < line["metrics"]["moe_local_pair_share"]["value"] < 70
    assert line["info"]["recorded_steps"] == 2 and line["info"]["problems"] == []
    assert any(".e_score_correction_bias" in leaf for leaf in line["info"]["leaves_left_out"])  # no gradient: left out of the change


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
    assert split["layers.0.mixer.in_proj.dt.w"].shape == (64, 8) and split["layers.0.mixer.in_proj.xBC.w"].shape == (64, 192)
    # the held experts stay one leaf a layer and projection (a leaf an expert cannot carry a limit: `leaves` says why)
    assert split["layers.1.mixer.experts.up_proj.w"].shape == (4, 64, 48) and split["layers.1.mixer.experts.down_proj.w"].shape == (4, 48, 64)
    total = sum(float(jnp.sum(jnp.square(v))) for v in split.values())
    assert total == pytest.approx(sum(float(jnp.sum(jnp.square(v))) for v in params.values()), rel=1e-6)
    assert float(jnp.abs(params["layers.1.mixer.gate.e_score_correction_bias"]).max()) == 0  # as assumed
    assert params["lm_head.w"].shape == params["embeddings"].shape and not bool(jnp.all(params["lm_head.w"] == params["embeddings"]))


# -- the FLOPs module ---------------------------------------------------------


def test_the_tiny_presets_work_is_hand_counted():
    cell = tiny()
    cfg, traffic = cell.config, cell.traffic
    # mamba: in_proj 64 x (128 + 192 + 8), out_proj 128 x 64; conv 192 x 4 + 192, dt_bias + A_log + D 24, gated norm 128
    # attention: q 64 x 128, k and v 64 x 64 each, o 128 x 64
    # experts: router 8 x 64 (+ 8 bias), shared 2 x 64 x 96, one expert 2 x 64 x 48, 4 held of 8, top-3: 1.5 pairs a token here
    mamba, attention, expert, shared, router = 20_992 + 8_192, 8_192 + 2 * 4_096 + 8_192, 6_144, 12_288, 512
    matmul = 2 * mamba + attention + 2 * (router + shared + 1.5 * expert) + 97 * 64
    held = 2 * (mamba + 960 + 24 + 128) + attention + 2 * (router + 8 + shared + 4 * expert) + 2 * 97 * 64 + 6 * 64
    assert flops.local_pairs_per_token(cfg) == 1.5
    assert flops.parameter_count(cfg) == {"matmul": matmul, "all": held}
    assert held == sum(math.prod(s) for s in ref.param_shapes(cfg, traffic).values())
    per_token = 6 * matmul + 6 * 1 * 64 * 128 + 3 * 2 * 5 * 8 * 16 * 16
    assert flops.required_flops_per_step(cfg, traffic) == per_token * 64 * 8
    need = flops.moe_experts_required_per_step(cfg, traffic)
    pairs = 64 * 8 * 1.5 * 2
    assert need["flops"] == 3 * 2 * 2 * 64 * 48 * pairs
    assert need["bytes"] == 2 * 4 * expert * 8 + pairs * 4 * 64 * 2


def test_the_real_files_work():
    cell = real()
    cfg, traffic = cell.config, cell.traffic
    count = flops.parameter_count(cfg)
    by_part = cfg["parameters"]["by_part"]
    assert count["all"] == 666_963_456 == cfg["parameters"]["count"]
    assert count["all"] == sum(math.prod(s) for s in ref.param_shapes(cfg, traffic).values())
    assert count["all"] == (4 * by_part["mamba_layer"] + 4 * by_part["expert_layer_8_held"] + by_part["attention_layer"]
                            + by_part["embedding_slice"] + by_part["head_slice"] + by_part["final_norm"])
    assert by_part["expert_layer_8_held"] == 8 * by_part["one_routed_expert"] + by_part["shared_expert"] + by_part["router_with_bias"] + 2688
    assert by_part["one_routed_expert"] == 2 * 2688 * 1856 and by_part["router_with_bias"] == 128 * 2688 + 128
    assert round(cfg["parameters"]["training_state_gb"], 2) == round(16 * count["all"] / 1e9, 2)
    # 318.4 M matmul parameters a token: the routed experts at 6 x 8 / 128 = 0.375 of one expert
    assert flops.local_pairs_per_token(cfg) == 0.375
    assert count["matmul"] == 4 * (2688 * 10_304 + 4096 * 2688) + (2 * 2688 * 4096 + 2 * 2688 * 256) \
        + 4 * (128 * 2688 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856) + 16_384 * 2688 == 318_431_232
    assert flops.scan_flops_per_token_layer(cfg) == 2_621_440  # 5 x 64 heads x 64 x 128
    step = flops.required_flops_per_step(cfg, traffic)
    assert step == (6 * 318_431_232 + 6 * 8192 * 4096 + 12 * 2_621_440) * 16_384
    assert 35.1e12 < step < 35.2e12
    need = flops.moe_experts_required_per_step(cfg, traffic)
    pairs = 16_384 * 6 * 8 / 128 * 4  # 6,144 a layer: 768 an expert
    assert pairs == 24_576 and need["flops"] == 3 * 2 * 2 * 2688 * 1856 * pairs
    assert need["bytes"] == 4 * 8 * 2 * 2688 * 1856 * 8 + pairs * 4 * 2688 * 2
    assert need["flops"] / 197e12 > need["bytes"] / 819e9  # bound by compute: 7.5 ms against 3.8 ms a step
    # at the pairs a seed's routing gave (the chip read about half of even) the work is halved and the weights'
    # bytes are not: under 11,233 pairs a step (351 an expert a layer) the bytes bind
    half = flops.moe_experts_required_per_step(cfg, traffic, pairs / 2)
    assert half["flops"] == need["flops"] / 2 and half["bytes"] == need["bytes"] - pairs / 2 * 4 * 2688 * 2
    for given, compute_bound in ((11_234, True), (11_233, False)):
        got = flops.moe_experts_required_per_step(cfg, traffic, given)
        assert (got["flops"] / 197e12 > got["bytes"] / 819e9) == compute_bound
    scan = flops.ssd_required_per_step(cfg, traffic)
    assert scan["flops"] == 3 * 2_621_440 * 16_384 * 4 and scan["bytes"] == (2 * (8192 + 2048) + 256 + 2 * (12_288 + 4096) + 512) * 16_384 * 4


# -- the entries --------------------------------------------------------------


def test_the_real_configuration_is_the_published_one_cut_as_it_says():
    cfg = real().config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else open(os.devnull) as f:
        rows = [json.loads(line) for line in f if "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16" in line]
    reduced = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    assert cfg["reduced"] == reduced and cfg["model_type"] == "nemotron_h" and cfg["family"] == "nemotron_h"
    assert cfg["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131_072,
                                "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"], cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (9, "MEMEM*EME", 8, 16_384)
    assert cfg["published"]["hybrid_override_pattern"].startswith(cfg["hybrid_override_pattern"]) and cfg["experts_held_first"] == 0
    widths = {"hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32, "num_key_value_heads": 2, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
              "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "intermediate_size": 1856,
              "num_experts_per_tok": 6, "n_shared_experts": 1, "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5}
    assert {k: cfg[k] for k in widths} == widths  # no width differs from the source
    for row in rows:  # where the catalog is at hand: every published key is in the file, changed only where `reduced` says
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k not in reduced} == {k: v for k, v in row["config"].items() if k not in reduced}
    assert "16 chips" in cfg["deployment"] and "over 8" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"initializer_range", "weights", "e_score_correction_bias", "auxiliary_loss", "in_proj_columns",
                                   "attention_positions", "precision"}
    assert cfg["memory"] == {"remat": "block"} and cfg["precision"]["control"] == "fp8"
    for key in ("system", "reference", "flops"):
        assert importlib.util.find_spec(cfg[key]) is not None, cfg[key]


def test_the_cell_loads_from_the_repos_benchmark_file():
    cell = real()
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    want = {"seq_len": 8192, "global_batch": 2, "chain_steps": 2, "steps_per_epoch": 8, "log_every": 50, "mesh": {"data": 1},
            "chips": 1, "check_steps": 2, "reference_block_rows": 1, "trace_slices": 1}
    assert {k: cell.traffic[k] for k in want} == want
    assert set(cell.traffic["limits"]) == {CONFIG} and set(cell.traffic["limits"][CONFIG]) <= {"loss_gap", "grad_gap", "delta_gap"}
    assert [m["name"] for m in cell.end_to_end] == ["step_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_METRICS) | {"step_mfu", "peak_hbm_gib", "device_idle_share", "program_load_s"} <= set(names)
    assert not {"ssd_time_share", "ssd_roofline", "remat_time_share", "flash_roofline"} & set(names)  # a `benchmark` PR's to append
    row = next(w for w in bench_file()["workloads"] if w["name"] == CELL)
    assert len(row["why"]) <= 200 and "1/16" in row["why"] and "98,304" in row["why"]


def test_the_accepted_entries_stand_as_they_were_before_the_new_ones():
    """By name and in order, each list: PR 35's entries first, this PR's after
    them (what a later PR appends comes after these and is not this test's)."""
    bench = bench_file()
    configs = ["gpt2-small", "vgg16-cifar10", "granite-4.0-h-micro"]
    workloads = ["gpt2s_t1024", "gpt2s_t4096", "vgg16_cifar_b4096", "gpt2s_t1024_dp4", "granite4h_t4096", "gpt2s_t8192"]
    per_layer = ["loop_overhead_share", "data_wait_share", "step_mfu", "flash_roofline", "flash_time_share", "peak_hbm_gib",
                 "device_idle_share", "idle_in_fetch_share", "idle_in_dispatch_share", "idle_in_glue_share", "stage_ms_per_step",
                 "produce_ms_per_step", "ring_empty_share", "loss_head_time_share", "flash_bwd_time_share", "program_load_s",
                 "collective_share", "collective_exposed_share", "ssd_time_share", "ssd_roofline", "remat_time_share"]
    assert [c["name"] for c in bench["configs"]][:4] == configs + [CONFIG]
    assert [w["name"] for w in bench["workloads"]][:7] == workloads + [CELL]
    assert [m["name"] for m in bench["per_layer"]][:25] == per_layer + NEW_METRICS
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    lm_cells = ["gpt2s_t1024", "gpt2s_t4096", "gpt2s_t1024_dp4"]
    assert all(lists[n] == ["granite4h_t4096"] for n in ("ssd_time_share", "ssd_roofline", "remat_time_share"))
    assert all(lists[n] == lm_cells for n in ("flash_roofline", "flash_time_share", "loss_head_time_share", "flash_bwd_time_share"))
    assert [m["name"] for m in bench["end_to_end"]] == ["step_ms", "setup_s"] and bench["run_seconds"] == 10


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(cells.BENCH_DIR, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metrics_file_declares_what_its_entry_says(name):
    bench = bench_file()
    assert [m["name"] for m in bench["per_layer"]].count(name) == 1  # the harness picks an entry by its name
    entry = dict(next(m for m in bench["per_layer"] if m["name"] == name))
    assert entry.pop("workloads") == [CELL]
    assert reader(name).DECLARATION == entry and entry["moves"] == "step_ms" and entry["unit"] == "%"
    assert entry["source"] == ("program_counter" if name == "moe_local_pair_share" else "device_trace")


# -- the readers on a toy trace ------------------------------------------------


def toy_ctx(ops, cell, peaks=PEAKS):
    """``ops``: (label, start ns, ns). A traced stretch of 10 µs on one device plane."""
    summary = trace.summarize([(DEV, [("XLA Ops", [(label, label + " 1.0", s, d) for label, s, d in ops])])], 1e-5)
    return {"trace": summary, "cfg": cell.config, "traffic": cell.traffic, "peaks": peaks, "chips": 1, "steps": 8, "trace_steps": 8}


def test_the_three_trace_readers_on_a_toy_trace():
    """A scope reaches a label here as it reaches an event's metadata on the
    chip. The layer's time is everything under `moe_layer`; what it spends on
    not multiplying is router + dispatch + combine, forward, recomputed and
    backward; the roofline's time is the grouped products' alone."""
    cell = real()
    fwd = "jit(chained)/jvp(HybridLM)/layer_1/moe/moe_layer/"
    again = "jit(chained)/transpose(jvp(HybridLM))/checkpoint/rematted_computation/layer_1/moe/moe_layer/"
    back = "jit(chained)/transpose(jvp(HybridLM))/layer_1/moe/moe_layer/"
    ops = [(f"%fusion.1 = fusion() {fwd}moe_router/dot_general", 0, 500), (f"%fusion.2 = fusion() {fwd}moe_dispatch/gather", 500, 500),
           (f"%ragged-dot.1 = custom-call() {fwd}moe_experts/ragged_dot", 1000, 1000),
           (f"%fusion.3 = fusion() {again}moe_experts/ragged_dot", 3000, 1000),
           (f"%fusion.4 = fusion() {back}moe_combine/moe_layer/moe_combine/gather", 5000, 500),
           (f"%fusion.5 = fusion() {back}shared_expert/dot_general", 6000, 1000),
           ("%fusion.6 = fusion() jit(chained)/jvp(HybridLM)/layer_0/mamba/mamba_mixer/ssd_scan/dot_general", 8000, 1000)]
    from benchmarks.lib import spans

    ctx = toy_ctx(ops, cell)
    assert reader("moe_time_share").read(ctx) == pytest.approx(45.0)
    assert reader("moe_dispatch_time_share").read(ctx) == pytest.approx(15.0)
    # the roofline's work is what the routing gave: the counter's pairs a step of the window, not an even share
    for pairs in (12_000.0, 24_576.0):
        ctx["spans_view"] = spans.View(spans=[], window=(0, 1), steps=8, idle_s=None, counters={"moe.pairs_local": 8 * pairs})
        need = flops.moe_experts_required_per_step(cell.config, cell.traffic, pairs)
        least = max(need["flops"] / 197e12, need["bytes"] / 819e9) * 8
        assert reader("moe_experts_roofline").read(ctx) == pytest.approx(100.0 * least / 2e-6)
    assert reader("moe_experts_roofline").read(dict(ctx, peaks=None)) is None  # off the chip nothing needs a peak
    ctx["spans_view"] = spans.View(spans=[], window=(0, 1), steps=8, idle_s=None, counters={})
    assert reader("moe_experts_roofline").read(ctx) is None  # a program that counts no pairs


def test_the_counter_reader_divides_the_programs_count_by_what_was_offered():
    from benchmarks.lib import spans

    cell = real()
    ctx = toy_ctx([], cell)
    offered = 16_384 * 6 * 4 * 8  # tokens x experts a token x expert layers x steps
    ctx["spans_view"] = spans.View(spans=[], window=(0, 1), steps=8, idle_s=None, counters={"moe.pairs_local": 0.0625 * offered})
    assert reader("moe_local_pair_share").read(ctx) == pytest.approx(6.25)
    ctx["spans_view"] = spans.View(spans=[], window=(0, 1), steps=8, idle_s=None, counters={"prefetch.fetches": 4})
    assert reader("moe_local_pair_share").read(ctx) is None  # a program that counts no pairs: the parent commit


def test_the_readers_return_nothing_where_the_program_lacks_the_scopes():
    """As on the parent commit with this PR's files laid over it, and in a cell of another family."""
    other = [("%flash_fwd.1 = custom-call() jit(chained)/jvp(TransformerLM)/flash_fwd", 0, 1000)]
    for name in NEW_METRICS[:3]:
        assert reader(name).read(toy_ctx(other, real())) is None, name
        assert reader(name).read({"trace": None, "peaks": PEAKS, "cfg": real().config, "traffic": {}}) is None, name
    experts = [("%fusion.1 = fusion() jit(chained)/jvp(X)/moe_layer/moe_experts/ragged_dot", 0, 1000)]
    assert reader("moe_experts_roofline").read(toy_ctx(experts, cells.load_cell("gpt2s_t8192"))) is None  # its FLOPs module reckons none
