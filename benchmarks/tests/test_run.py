"""The harness end to end at tiny sizes on the CPU, kernels interpreted: the
result line's keys, the refusal to call a CPU run a result, a data=4 cell and
a configuration of a new family added by files alone, and `correct` coming
out false for the control and for each planted fault a training cell can have."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import cells, flops, harness, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = os.path.join(DATA, "BENCHMARK.json")
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(autouse=True)
def no_recorder_left_behind():
    """A traced run installs the program's span recorder process-wide; the
    next test file's readers must not find this one's spans."""
    yield
    from distributed_training_pytorch_tpu import profiling

    profiling.uninstall_recorder()


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


# A family the benchmark does not know: a two-layer MLP on token windows (each
# position predicts the next token from its own embedding). Its trainer, its
# plain reference and its FLOPs module are files of its own, as a `model_config`
# PR would bring them; BENCHMARK.json gains entries and nothing is edited.
NEW_FAMILY = {
    "mlpfam_system.py": '''
import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from benchmarks.systems.common import StepLosses, trainer_kwargs
from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.ops import warmup_cosine_lr
from distributed_training_pytorch_tpu.trainer import Trainer


class TokenMLP(nn.Module):
    vocab: int
    width: int
    hidden: int

    @nn.compact
    def __call__(self, tokens, train=False):
        x = nn.Embed(self.vocab, self.width)(tokens)
        x = jax.nn.gelu(nn.Dense(self.hidden)(x))
        return nn.Dense(self.vocab)(x)


class MLPTrainer(StepLosses, Trainer):
    criterion_uses_mask = True

    def __init__(self, windows, cfg, **kw):
        self.windows, self.cfg = windows, cfg
        super().__init__(**kw)

    def build_train_dataset(self):
        return ArrayDataSource(image=self.windows[:, :-1], label=self.windows[:, 1:])

    def build_val_dataset(self):
        return ArrayDataSource(image=self.windows[:1, :-1], label=self.windows[:1, 1:])

    def build_model(self):
        return TokenMLP(self.cfg["vocab_size"], self.cfg["width"], self.cfg["hidden"])

    def build_criterion(self):
        def criterion(logits, batch):
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), batch["label"][..., None], axis=-1)[..., 0]
            loss = jnp.mean(jnp.mean(nll, axis=-1))
            return loss, {"nll": loss}

        return criterion

    def build_scheduler(self):
        return warmup_cosine_lr(self.cfg["optimizer"]["lr"], self.max_epoch,
                                max(1, len(self.train_dataset) // self.batch_size), warmup_epochs=1)

    def build_optimizer(self, schedule):
        opt = self.cfg["optimizer"]
        return optax.adamw(schedule, weight_decay=opt["weight_decay"], b1=opt["b1"], b2=opt["b2"])

    def build_example_input(self):
        return jnp.zeros((1, self.windows.shape[1] - 1), jnp.int32)


def prepare():
    pass


def build(cfg, traffic, data, **common):
    return MLPTrainer(data["windows"], cfg, **trainer_kwargs(cfg, traffic, **common))


def expect_kernels(cfg, on_tpu):
    return []
''',
    "mlpfam_reference.py": '''
import jax
import jax.numpy as jnp

from benchmarks.reference.common import HI, ROUNDERS


def init_params(cfg, traffic, key):
    v, d, h = cfg["vocab_size"], cfg["width"], cfg["hidden"]
    k = jax.random.split(key, 3)
    return {"emb": 0.5 * jax.random.normal(k[0], (v, d), jnp.float32),
            "fc1.w": d ** -0.5 * jax.random.normal(k[1], (d, h), jnp.float32), "fc1.b": jnp.zeros((h,), jnp.float32),
            "fc2.w": h ** -0.5 * jax.random.normal(k[2], (h, v), jnp.float32), "fc2.b": jnp.zeros((v,), jnp.float32)}


def to_program(p, cfg):
    return {"Embed_0": {"embedding": p["emb"]}, "Dense_0": {"kernel": p["fc1.w"], "bias": p["fc1.b"]},
            "Dense_1": {"kernel": p["fc2.w"], "bias": p["fc2.b"]}}


def from_program(tree, cfg):
    return {"emb": tree["Embed_0"]["embedding"], "fc1.w": tree["Dense_0"]["kernel"], "fc1.b": tree["Dense_0"]["bias"],
            "fc2.w": tree["Dense_1"]["kernel"], "fc2.b": tree["Dense_1"]["bias"]}


def loss_sum(p, batch, cfg, control=None):
    rnd = ROUNDERS[control]
    x = p["emb"][batch["image"]]
    x = jax.nn.gelu(jnp.matmul(rnd(x), rnd(p["fc1.w"]), precision=HI) + p["fc1.b"])
    logits = jnp.matmul(rnd(x), rnd(p["fc2.w"]), precision=HI) + p["fc2.b"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, batch["label"][..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.mean(nll, axis=-1))
''',
    "mlpfam_flops.py": '''
def required_flops_per_step(cfg, traffic):
    """Forward + backward of the two matmuls: 6 x their parameters a token."""
    return 6 * (cfg["width"] * cfg["hidden"] + cfg["hidden"] * cfg["vocab_size"]) * traffic["seq_len"] * traffic["global_batch"]
''',
}
MLP_REQUIRED = 6 * (32 * 64 + 64 * 256) * 64 * 16


@pytest.fixture
def new_family(tmp_path, monkeypatch):
    """(bench file, data dir, configuration) of the benchmark with the new
    family's cell added: new files in a directory of its own, new entries."""
    extra = tmp_path / "extra"
    for sub in ("traffic", "configs"):
        (extra / sub).mkdir(parents=True)
    for name, text in NEW_FAMILY.items():
        (extra / name).write_text(text)
    monkeypatch.syspath_prepend(str(extra))
    lm = json.load(open(os.path.join(DATA, "configs", "lm-tiny.json")))
    cfg = {"name": "mlp-tiny", "source": "test preset, not a published model", "family": "token-mlp",
           "system": "mlpfam_system", "reference": "mlpfam_reference", "flops": "mlpfam_flops",
           "vocab_size": 256, "width": 32, "hidden": 64, "input": {"kind": "token_windows"},
           "precision": lm["precision"], "optimizer": lm["optimizer"]}
    (extra / "configs" / "mlp-tiny.json").write_text(json.dumps(cfg))
    mix = json.load(open(os.path.join(DATA, "traffic", "tiny_t128_b8.json")))
    mix.update(seq_len=64, limits={"mlp-tiny": {"loss_gap": 2e-6, "grad_gap": 2e-5, "delta_gap": 1e-5}})  # program 2e-7, control 1e-4 on grad_gap
    (extra / "traffic" / "mlp_t64_b16.json").write_text(json.dumps(mix))
    bench = json.load(open(BENCH))
    for c in bench["configs"]:
        c["file"] = os.path.join(DATA, c["file"])
    bench["configs"].append({"name": "mlp-tiny", "source": "test", "file": str(extra / "configs" / "mlp-tiny.json"),
                             "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "mlp_tiny", "config": "mlp-tiny", "traffic": "mlp_t64_b16", "chips": 1, "why": "t"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path / "BENCHMARK.json"), str(extra), cfg


def test_a_new_family_is_files_and_entries_only(new_family):
    """What a `model_config` PR must be able to do: a configuration whose
    family the benchmark has never heard of, with its own system, reference
    and FLOPs module, runs traced to `correct` true, and the whole step's
    required FLOPs are its module's."""
    bench_file, extra, cfg = new_family
    result = harness.run_cell("mlp_tiny", 2**31 + 23, 0.5, True, require_tpu=False,
                              bench_file=bench_file, data_dirs=[extra, DATA])
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    traffic = cells.load_cell("mlp_tiny", bench_file, [extra, DATA]).traffic
    assert flops.required_flops_per_step(cfg, traffic) == MLP_REQUIRED
    assert flops.flash_required_per_step(cfg, traffic) is None
    # the control (bfloat16 under the stated float32) is not correct here either
    control = harness.run_cell("mlp_tiny", 2**31 + 23, 0.5, False, require_tpu=False, stand_in="control",
                               bench_file=bench_file, data_dirs=[extra, DATA])
    assert control["correct"] is False


PEAKS = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e10}  # made up: off the chip the readers return before they reckon


@pytest.mark.parametrize("workload,required,flash", [
    # 6 x (2 x (4 x 128^2 + 2 x 128 x 512) + 512 x 128) + 6 x 2 x 128 x 128 a token, 128 x 16 tokens
    ("lm_tiny", (6 * 458_752 + 196_608) * 2048, True),
    ("vgg_tiny", None, False),
    ("mlp_tiny", MLP_REQUIRED, False),
])
def test_the_whole_step_and_flash_readers_reckon_by_the_configurations_module(new_family, workload, required, flash):
    """`step_mfu` is read in every cell and `flash_roofline` wherever the trace
    holds a Mosaic call: with peaks (on the chip) neither may trip over a
    family it has no function for."""
    bench_file, extra, _ = new_family
    cell = cells.load_cell(workload, bench_file, [extra, DATA])
    ops = [("%custom-call.7 = bf16[8] custom-call(bf16[8] %p), custom_call_target=\"tpu_custom_call\"",) * 2 + (0.0, 500.0)]
    ctx = {"cfg": cell.config, "traffic": cell.traffic, "peaks": PEAKS, "chips": 1, "steps": 6,
           "window_s": 3.0, "trace_steps": 3, "trace": trace.summarize([("/device:TPU:0", [("XLA Ops", ops)])], 1e-6)}
    need = flops.required_flops_per_step(cell.config, cell.traffic)
    if required is not None:
        assert need == required
    declared = [m for m in cell.per_layer if m["name"] in ("step_mfu", "flash_roofline")]
    metrics = harness.read_per_layer(dataclasses.replace(cell, per_layer=declared), ctx)
    assert metrics["step_mfu"]["value"] == pytest.approx(100.0 * need * 6 / 3.0 / 1e12)
    assert ("flash_roofline" in metrics) is flash
    if flash:
        reckoned = flops.flash_required_per_step(cell.config, cell.traffic)
        least = max(reckoned["flops"] / 1e12, reckoned["bytes"] / 1e10)
        assert metrics["flash_roofline"]["value"] == pytest.approx(100.0 * least * 3 / 500e-9)


@pytest.mark.parametrize("placed,cap_kept", [(None, False), ("elsewhere", True)])
def test_a_cap_from_outside_never_caps_the_checkouts_own_cache(tmp_path, monkeypatch, placed, cap_kept):
    """The chip tool's machines bring JAX_COMPILATION_CACHE_MAX_SIZE for their own directory; under it the
    four-chip cell's programs and a stand-in's evicted each other in the calibration (PERF.md section 6, PR 27)."""
    before = {k: getattr(jax.config, k) for k in ("jax_compilation_cache_dir", "jax_compilation_cache_max_size")}
    try:
        jax.config.update("jax_compilation_cache_max_size", 1 << 20)  # as jax reads the variable at import
        if placed:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / placed))
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = harness.place_compile_cache()
        assert path == (str(tmp_path / placed) if placed else os.path.join(cells.ROOT, ".jax_cache"))
        assert jax.config.jax_compilation_cache_max_size == ((1 << 20) if cap_kept else -1)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


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


def no_exchange(trainer):
    """The exchange between chips left out: every chip would step on the mean
    gradient of its own rows. Under jit the exchange is the compiler's, so the
    fault is planted as what the first chip's copy then holds: the loss over
    its shard of the batch alone."""
    loss_fn, chips = trainer.engine.loss_fn, trainer.mesh.devices.size

    def local(params, model_state, batch, rng, train):
        rows = batch["label"].shape[0] // chips
        return loss_fn(params, model_state, {k: v[:rows] for k, v in batch.items()}, rng, train)

    trainer.engine.loss_fn = local


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("lm_tiny", state_unchanged, "delta_gap"),
    ("lm_tiny", half_batch, "grad_gap"),
    ("vgg_tiny", state_unchanged, "delta_gap"),
    ("vgg_tiny", half_batch, "grad_gap"),
    ("lm_tiny_dp4", no_exchange, "grad_gap"),
    ("lm_tiny_dp4", half_batch, "grad_gap"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, caught_by):
    result = run(workload, fault=fault)
    assert result["correct"] is False
    check = result["checks"][caught_by]
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("workload,stand_in", [("lm_tiny", "control"), ("vgg_tiny", "control"),
                                               ("lm_tiny_dp4", "control"), ("lm_tiny_dp4", "no_exchange")])
def test_the_lower_precision_control_is_not_correct(workload, stand_in):
    """The reference one precision down (bfloat16 under the presets' stated
    float32), or with a chip's rows alone, put in the program's place."""
    result = run(workload, stand_in=stand_in)
    assert result["correct"] is False


def test_the_four_chip_cell_runs_traced_and_leaves_the_collective_shares_out():
    """`lm_tiny_dp4` declares `collective_share` and `collective_exposed_share`;
    a CPU trace has no device plane, so they read nothing and are left out.
    The reference takes its blocks split by row over the four devices."""
    line = check_line(run("lm_tiny_dp4", trace=True), ["loop_overhead_share", "data_wait_share"])
    assert line["correct"] is True and line["device"]["count"] == 4
    cell = cells.load_cell("lm_tiny_dp4", BENCH, [DATA])
    assert {"collective_share", "collective_exposed_share"} <= {m["name"] for m in cell.per_layer}
