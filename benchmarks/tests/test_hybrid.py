"""The hybrid state-space / attention configuration's files: its trainer glue
and plain reference through the harness at a tiny float32 size on the CPU
(``data_hybrid/``: a benchmark file, a configuration and a traffic mix of its
own, so that no file the benchmark has is edited), its FLOPs module held to
hand-counted numbers, the two cells of PR 34 as the repo's BENCHMARK.json
declares them, and the three per-layer readers on a toy trace."""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.flops import hybrid_lm as hybrid_flops
from benchmarks.lib import cells, harness, trace
from benchmarks.reference import granite_hybrid as ref

DATA = os.path.join(os.path.dirname(__file__), "data_hybrid")
BENCH = os.path.join(DATA, "BENCHMARK.json")
NEW_METRICS = ["ssd_time_share", "ssd_roofline", "remat_time_share"]
DEV = "/device:TPU:0"


@pytest.fixture(autouse=True)
def no_recorder_left_behind():
    yield
    from distributed_training_pytorch_tpu import profiling

    profiling.uninstall_recorder()


def run(stand_in=None, trace=False, seed=2**31 + 5):
    return harness.run_cell("hybrid_tiny", seed, 0.5, trace, require_tpu=False, bench_file=BENCH, data_dirs=[DATA],
                            stand_in=stand_in)


def tiny():
    return cells.load_cell("hybrid_tiny", BENCH, [DATA])


def real(name="granite4h_t4096"):
    return cells.load_cell(name)


# -- through the harness ------------------------------------------------------


def test_the_tiny_hybrid_is_correct_through_the_harness():
    """Float32 program against the float32 reference: round-off (2e-7 here),
    far under limits that the control and the planted fault are far over."""
    line = run(trace=True)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert all(c["value"] < 1e-5 for c in line["checks"].values()), line["checks"]
    # a CPU trace has no device plane: nothing is reported under a device metric's name
    assert not set(NEW_METRICS) & set(line["metrics"])
    assert line["info"]["recorded_steps"] == 2


@pytest.mark.parametrize("stand_in,over", [("control", ["grad_gap", "delta_gap"]), ("half_batch", ["loss_gap", "grad_gap", "delta_gap"])])
def test_the_control_and_the_planted_fault_are_not_correct(stand_in, over):
    """bfloat16 operands under the stated float32 (2.8e-3 on the worst leaf's
    first moment) and half of the rows left out (0.4), in the program's place."""
    line = run(stand_in=stand_in)
    assert line["correct"] is False
    for key in over:
        assert line["checks"][key]["value"] > line["checks"][key]["limit"], (key, line["checks"][key])


def test_the_reference_and_the_program_agree_on_the_tree():
    cfg = tiny().config
    params = ref.init_params(cfg, {}, jax.random.key(0))
    assert {k: v.shape for k, v in params.items()} == ref.param_shapes(cfg, {})
    back = ref.from_program(ref.to_program(params, cfg), cfg)
    assert set(back) == set(params) and all(back[k] is params[k] for k in params)
    split = ref.leaves(params, cfg)
    assert split["layers.0.mamba.in_proj.dt.w"].shape == (64, 8) and split["layers.0.mamba.in_proj.xBC.w"].shape == (64, 160)
    assert split["layers.2.shared_mlp.input_linear.a.w"].shape == (64, 128) and "layers.0.mamba.in_proj.w" not in split
    total = sum(float(jnp.sum(jnp.square(v))) for v in split.values())
    assert total == pytest.approx(sum(float(jnp.sum(jnp.square(v))) for v in params.values()), rel=1e-6)
    # the scan's own parameters as Mamba-2 initialises them
    assert float(jnp.min(jnp.exp(params["layers.0.mamba.A_log"]))) >= 1 and float(jnp.max(jnp.exp(params["layers.0.mamba.A_log"]))) <= 16
    dt = jax.nn.softplus(params["layers.0.mamba.dt_bias"])
    assert 1e-3 * 0.999 <= float(jnp.min(dt)) and float(jnp.max(dt)) <= 1e-1 * 1.001
    assert float(jnp.max(jnp.abs(params["layers.0.mamba.conv1d.w"]))) <= 0.5


# -- the FLOPs module ---------------------------------------------------------


def test_the_tiny_presets_work_is_hand_counted():
    cell = tiny()
    cfg, traffic = cell.config, cell.traffic
    # mamba: in_proj 64 x (128 + 160 + 8), out_proj 128 x 64; conv 160 x 4 + 160, dt_bias + A_log + D 24, gated norm 128
    # attention: q 64 x 64, k and v 64 x 32 each, o 64 x 64; MLP 64 x 256 + 128 x 64; embedding 97 x 64
    matmul = 3 * (18_944 + 8_192 + 24_576) + (12_288 + 24_576) + 6_208
    assert hybrid_flops.parameter_count(cfg) == {"matmul": matmul, "all": matmul + 3 * 952 + 4 * 2 * 64 + 64}
    assert hybrid_flops.parameter_count(cfg)["all"] == sum(math.prod(s) for s in ref.param_shapes(cfg, traffic).values())
    assert hybrid_flops.scan_flops_per_token_layer(cfg) == 5 * 8 * 16 * 16
    per_token = 6 * matmul + 6 * 1 * 64 * 64 + 3 * 3 * 10_240
    assert hybrid_flops.required_flops_per_step(cfg, traffic) == per_token * 64 * 8
    need = hybrid_flops.ssd_required_per_step(cfg, traffic)
    assert need["flops"] == 3 * 10_240 * 64 * 8 * 3
    assert need["bytes"] == (2 * (2 * 128 + 32) + 4 * 8 + 2 * (3 * 128 + 64) + 4 * 16) * 64 * 8 * 3


def test_the_real_files_work():
    cell = real()
    cfg, traffic = cell.config, cell.traffic
    count = hybrid_flops.parameter_count(cfg)
    assert count["all"] == 772_160_448 == cfg["parameters"]["count"]
    assert count["all"] == sum(math.prod(s) for s in ref.param_shapes(cfg, traffic).values())
    assert count["all"] - count["matmul"] == 9 * (4352 * 5 + 3 * 64 + 4096) + 10 * 2 * 2048 + 2048
    assert hybrid_flops.scan_flops_per_token_layer(cfg) == 2_621_440  # 5 x 64 heads x 64 x 128
    need = hybrid_flops.ssd_required_per_step(cfg, traffic)
    assert need["flops"] == 3 * 2_621_440 * 8192 * 9
    assert need["bytes"] == (17_152 + 26_112) * 8192 * 9  # forward 17 KB a token a layer: bound by memory
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    step = hybrid_flops.required_flops_per_step(cfg, traffic)
    assert step == (6 * count["matmul"] + 6 * 4096 * 2048 + 27 * 2_621_440) * 8192
    assert 38.9e12 < step < 39.0e12


# -- the entries --------------------------------------------------------------


def test_the_real_configuration_is_the_published_one_cut_as_it_says():
    cfg = real().config
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12_544) and cfg["published"]["vocab_size"] == 8 * 12_544
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4 and cfg["published"]["num_hidden_layers"] == 40
    widths = {"hidden_size": 2048, "shared_intermediate_size": 8192, "intermediate_size": 8192, "num_attention_heads": 32,
              "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
              "mamba_expand": 2, "mamba_n_groups": 1, "mamba_chunk_size": 256, "num_experts_per_tok": 0}
    assert {k: cfg[k] for k in widths} == widths  # no width differs from the source
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"], cfg["attention_multiplier"], cfg["logits_scaling"]) \
        == (12, 0.22, 0.015625, 8)
    assert cfg["memory"] == {"remat": "block"} and cfg["precision"]["control"] == "fp8"
    for key in ("system", "reference", "flops"):
        assert importlib.util.find_spec(cfg[key]) is not None, cfg[key]


@pytest.mark.parametrize("name,config,traffic", [
    ("granite4h_t4096", "granite-4.0-h-micro", {"seq_len": 4096, "global_batch": 2}),
    ("gpt2s_t8192", "gpt2-small", {"seq_len": 8192, "global_batch": 4}),
])
def test_both_new_cells_load_from_the_repos_benchmark_file(name, config, traffic):
    cell = real(name)
    assert cell.chips == 1 and cell.config["name"] == config
    want = dict(traffic, chain_steps=2, steps_per_epoch=8, log_every=50, mesh={"data": 1}, chips=1, check_steps=2,
                reference_block_rows=1, trace_slices=1)
    assert {k: cell.traffic[k] for k in want} == want
    assert set(cell.traffic["limits"]) == {config} and set(cell.traffic["limits"][config]) <= {"loss_gap", "grad_gap", "delta_gap"}
    assert [m["name"] for m in cell.end_to_end] == ["step_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert {"step_mfu", "peak_hbm_gib", "device_idle_share", "program_load_s"} <= set(names)
    assert (set(NEW_METRICS) <= set(names)) is (name == "granite4h_t4096")
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]][-2:] == ["granite4h_t4096", "gpt2s_t8192"]  # appended
    assert all(len(w["why"]) <= 200 for w in bench["workloads"]) and all(len(c["why"]) <= 200 for c in bench["configs"])


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(cells.BENCH_DIR, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metrics_file_declares_what_its_entry_says(name):
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"]].count(name) == 1  # the harness picks an entry by its name
    entry = dict(next(m for m in bench["per_layer"] if m["name"] == name))
    assert entry.pop("workloads") == ["granite4h_t4096"]
    assert reader(name).DECLARATION == entry and entry["moves"] == "step_ms" and entry["source"] == "device_trace"


def test_the_new_entries_end_the_list_after_the_accepted_ones_as_they_were():
    """A PR that changes the program appends to ``per_layer``. ``test_spans.py``'s
    pin of the list's last eleven names (a file PR 34 may not edit) therefore
    fails on its first assertion; what it went on to check holds, and is held here."""
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    accepted = ["idle_in_fetch_share", "idle_in_dispatch_share", "idle_in_glue_share", "stage_ms_per_step",
                "produce_ms_per_step", "ring_empty_share", "loss_head_time_share", "flash_bwd_time_share",
                "program_load_s", "collective_share", "collective_exposed_share"]
    assert [m["name"] for m in bench["per_layer"]][-len(accepted) - 3:] == accepted + NEW_METRICS
    lm_cells = ["gpt2s_t1024", "gpt2s_t4096", "gpt2s_t1024_dp4"]
    lists = {"loss_head_time_share": lm_cells, "flash_bwd_time_share": lm_cells,
             "collective_share": ["gpt2s_t1024_dp4"], "collective_exposed_share": ["gpt2s_t1024_dp4"]}
    for entry in bench["per_layer"]:
        if entry["name"] in accepted:
            entry = dict(entry)
            assert entry.pop("workloads", None) == lists.get(entry["name"]), entry["name"]
            assert reader(entry["name"]).DECLARATION == entry and entry["moves"] in ("step_ms", "setup_s"), entry["name"]


# -- the readers on a toy trace ------------------------------------------------


def toy_ctx(ops, cell, peaks):
    """``ops``: (label, start ns, ns). A traced stretch of 10 µs on one device plane."""
    summary = trace.summarize([(DEV, [("XLA Ops", [(label, label + " 1.0", s, d) for label, s, d in ops])])], 1e-5)
    return {"trace": summary, "cfg": cell.config, "traffic": cell.traffic, "peaks": peaks, "chips": 1,
            "steps": 8, "trace_steps": 8}


def test_the_three_readers_on_a_toy_trace():
    """A scope reaches a label here as it reaches an event's metadata on the
    chip; the scan's forward, recomputation and backward all count as the
    scan's, and only the recomputation as rematerialised."""
    cell = real()
    scan = "jit(chained)/jvp(HybridLM)/layer_0/mamba/mamba_mixer/ssd_scan/dot_general"
    again = "jit(chained)/transpose(jvp(HybridLM))/layer_0/rematted_computation/mamba/mamba_mixer/ssd_scan/dot_general"
    back = "jit(chained)/transpose(jvp(HybridLM))/layer_0/mamba/mamba_mixer/ssd_scan/transpose/dot_general"
    other = "jit(chained)/transpose(jvp(HybridLM))/layer_0/rematted_computation/gated_mlp/dot_general"
    ops = [(f"%fusion.1 = fusion() {scan}", 0, 1000), (f"%fusion.2 = fusion() {again}", 2000, 1000),
           (f"%fusion.3 = fusion() {back}", 4000, 2000), (f"%fusion.4 = fusion() {other}", 7000, 500)]
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = toy_ctx(ops, cell, peaks)
    assert reader("ssd_time_share").read(ctx) == pytest.approx(40.0)
    assert reader("remat_time_share").read(ctx) == pytest.approx(15.0)
    need = hybrid_flops.ssd_required_per_step(cell.config, cell.traffic)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9) * 8
    assert reader("ssd_roofline").read(ctx) == pytest.approx(100.0 * least / 4e-6)
    assert reader("ssd_roofline").read(dict(ctx, peaks=None)) is None  # off the chip nothing needs a peak


def test_the_readers_return_nothing_where_the_program_lacks_the_scopes():
    """As on the parent commit, and in a cell whose configuration reckons no scan."""
    flash_only = [("%flash_fwd.1 = custom-call() jit(chained)/jvp(TransformerLM)/flash_fwd", 0, 1000)]
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    for name in NEW_METRICS:
        assert reader(name).read(toy_ctx(flash_only, real(), peaks)) is None, name
        assert reader(name).read({"trace": None, "peaks": peaks, "cfg": real().config, "traffic": {}}) is None, name
    scan = [("%fusion.1 = fusion() jit(chained)/jvp(X)/ssd_scan/dot_general", 0, 1000)]
    assert reader("ssd_roofline").read(toy_ctx(scan, real("gpt2s_t8192"), peaks)) is None
