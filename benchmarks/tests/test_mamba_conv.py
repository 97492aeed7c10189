"""PR 39's per-layer metric `mamba_conv_time_share` on a toy trace, beside
`ssd_time_share`'s case in ``test_hybrid.py``: the convolution's forward, its
recomputation and its backward all count (the kernels' and the parent's
``jax.numpy`` form's alike, by the scope), nothing else does, and a trace
without the scope reads nothing. Its entry ends `per_layer` and names the two
hybrid cells."""

import importlib.util
import json
import os

import pytest

from benchmarks.lib import cells, trace

DEV = "/device:TPU:0"
CELLS = ["granite4h_t4096", "nemotron3nano_t8192"]


def reader():
    path = os.path.join(cells.BENCH_DIR, "metrics", "mamba_conv_time_share.py")
    spec = importlib.util.spec_from_file_location("mamba_conv_time_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def toy_ctx(ops, cell):
    """``ops``: (label, start ns, ns). A traced stretch of 10 µs on one device plane."""
    summary = trace.summarize([(DEV, [("XLA Ops", [(label, label + " 1.0", s, d) for label, s, d in ops])])], 1e-5)
    return {"trace": summary, "cfg": cell.config, "traffic": cell.traffic, "chips": 1, "steps": 8, "trace_steps": 8,
            "peaks": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("cell", CELLS)
def test_the_forward_the_recomputation_and_the_backward_count(cell):
    mixer = "layer_0/mamba/mamba_mixer"
    fwd = f"jit(chained)/jvp(HybridLM)/{mixer}/mamba_conv/jit(_fwd_call)/mamba_conv/conv_fwd"
    again = f"jit(chained)/transpose(jvp(HybridLM))/layer_0/rematted_computation/{mixer.split('/', 1)[1]}/mamba_conv/jit(_fwd_call)/mamba_conv/conv_fwd"
    # the custom_vjp's backward half: only the scope the wrapper opens itself reaches it
    back = "jit(chained)/transpose(jvp(HybridLM))/jit(_bwd_call)/mamba_conv/conv_bwd"
    parent = f"jit(chained)/transpose(jvp(HybridLM))/{mixer}/mamba_conv/mul"  # the jax.numpy form's transpose
    other = f"jit(chained)/jvp(HybridLM)/{mixer}/ssd_scan/jit(_fwd_call)/ssd_scan/ssd_fwd"
    ops = [(f"%conv_fwd.1 = custom-call() {fwd}", 0, 1000), (f"%conv_fwd.2 = custom-call() {again}", 1000, 1000),
           (f"%conv_bwd.1 = custom-call() {back}", 2000, 1500), (f"%fusion.1 = fusion() {parent}", 4000, 500),
           (f"%ssd_fwd.1 = custom-call() {other}", 5000, 2000)]
    assert reader().read(toy_ctx(ops, cells.load_cell(cell))) == pytest.approx(40.0)


def test_a_trace_without_the_scope_reads_nothing():
    flash_only = [("%flash_fwd.1 = custom-call() jit(chained)/jvp(TransformerLM)/flash_fwd", 0, 1000)]
    assert reader().read(toy_ctx(flash_only, cells.load_cell("gpt2s_t4096"))) is None
    assert reader().read({"trace": None}) is None


def test_the_entry_ends_the_list_and_says_what_the_reader_says():
    with open(os.path.join(os.path.dirname(cells.BENCH_DIR), "BENCHMARK.json")) as f:
        entry = dict(json.load(f)["per_layer"][-1])
    assert entry.pop("workloads") == CELLS
    assert entry == reader().DECLARATION
    for cell in CELLS:
        assert "mamba_conv_time_share" in [m["name"] for m in cells.load_cell(cell).per_layer]
    assert "mamba_conv_time_share" not in [m["name"] for m in cells.load_cell("gpt2s_t4096").per_layer]
