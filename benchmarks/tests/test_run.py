"""The harness end to end at tiny sizes on the CPU, kernels interpreted: the
result line's keys, the refusal to call a CPU run a result, a data=4 cell
added by files alone, and `correct` coming out false for the control and for
each planted fault a training cell can have."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import cells, harness

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = os.path.join(DATA, "BENCHMARK.json")
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run(workload, seed=2**31 + 11, trace=False, **kw):
    return harness.run_cell(workload, seed, 0.5, trace, require_tpu=False, bench_file=BENCH, data_dirs=[DATA], **kw)


def check_line(result, metric_names):
    line = json.loads(json.dumps(result))
    assert [k for k in CONTRACT_KEYS if k in line] == CONTRACT_KEYS
    assert list(line)[-1] == "checks" and set(line["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    for c in line["checks"].values():
        assert {"value", "limit"} <= set(c)
    assert set(line["metrics"]) == set(metric_names)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["platform"] == "cpu" and line["attempted"] > 0 and line["failed"] == 0
    return line


def test_lm_cell_end_to_end():
    line = check_line(run("lm_tiny"), ["step_ms", "setup_s"])
    assert line["correct"] is True
    assert line["info"]["leaves_left_out"] == ["h0.attn.c_attn.k.b", "h1.attn.c_attn.k.b"]
    assert line["attempted"] % 3 == 0  # whole slices: 3-step epochs (a chained window of 2 and a single-step tail)


def test_vgg_cell_traced_run_reports_only_what_it_could_read():
    line = check_line(run("vgg_tiny", trace=True), ["loop_overhead_share", "data_wait_share"])
    assert line["correct"] is True
    # no TPU plane in a CPU trace: nothing is reported under a device metric's name
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_a_data4_cell_is_files_and_entries_only(tmp_path):
    """A later PR adds a traffic mix, a cell and a per-layer metric without
    editing a file: new files in a directory of its own, new entries in
    BENCHMARK.json."""
    extra = tmp_path / "extra"
    (extra / "traffic").mkdir(parents=True)
    (extra / "metrics").mkdir()
    mix = json.load(open(os.path.join(DATA, "traffic", "tiny_t128_b8.json")))
    mix.update(mesh={"data": 4}, chips=4)
    (extra / "traffic" / "added_dp4.json").write_text(json.dumps(mix))
    (extra / "metrics" / "steps_counted.py").write_text("def read(ctx):\n    return ctx['steps']\n")
    bench = json.load(open(BENCH))
    for c in bench["configs"]:
        c["file"] = os.path.join(DATA, c["file"])
    bench["workloads"].append({"name": "added_dp4", "config": "lm-tiny", "traffic": "added_dp4", "chips": 4, "why": "t"})
    bench["per_layer"].append({"name": "steps_counted", "unit": "steps", "better": "higher", "source": "program_counter",
                               "layer": "trainer epoch loop", "moves": "step_ms", "workloads": ["added_dp4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result = harness.run_cell("added_dp4", 5, 0.5, True, require_tpu=False,
                              bench_file=str(tmp_path / "BENCHMARK.json"), data_dirs=[str(extra), DATA])
    assert result["correct"] is True and result["device"]["count"] == 4
    assert result["metrics"]["steps_counted"]["value"] == result["attempted"]


def test_the_command_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(cells.BENCH_DIR, "run.py"), "--workload", "gpt2s_t1024",
                          "--seed", "1", "--seconds", "1", "--trace", "0"], capture_output=True, text=True,
                         env=env, cwd=cells.ROOT, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == "" and "not a TPU" in out.stderr


def test_the_command_refuses_a_bare_directory(tmp_path):
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(cells.BENCH_DIR, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", "gpt2s_t1024", "--seed", "1",
                          "--seconds", "1", "--trace", "0"], capture_output=True, text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""


# -- the timed path broken underneath ---------------------------------------


def state_unchanged(trainer):
    """A step that returns its state unchanged (the counter aside)."""
    engine = trainer.engine
    chained, single = engine.train_steps_chained, engine.train_step

    def keep(call):
        def wrapped(state, *args):
            held = jax.tree.map(jnp.copy, (state.params, state.opt_state))
            new, metrics = call(state, *args)
            return new.replace(params=held[0], opt_state=held[1]), metrics
        return wrapped

    engine.train_steps_chained, engine.train_step = keep(chained), keep(single)


def half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest."""
    loss_fn = trainer.engine.loss_fn

    def halved(params, model_state, batch, rng, train):
        rows = batch["label"].shape[0] // 2
        return loss_fn(params, model_state, {k: v[:rows] for k, v in batch.items()}, rng, train)

    trainer.engine.loss_fn = halved


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("lm_tiny", state_unchanged, "delta_gap"),
    ("lm_tiny", half_batch, "grad_gap"),
    ("vgg_tiny", state_unchanged, "delta_gap"),
    ("vgg_tiny", half_batch, "grad_gap"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, caught_by):
    result = run(workload, fault=fault)
    assert result["correct"] is False
    check = result["checks"][caught_by]
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("workload", ["lm_tiny", "vgg_tiny"])
def test_the_lower_precision_control_is_not_correct(workload):
    """The reference one precision down (bfloat16 under the presets' stated
    float32), put in the program's place."""
    result = run(workload, stand_in="control")
    assert result["correct"] is False
