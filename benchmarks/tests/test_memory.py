"""What a run costs the device beyond the program: nothing of parameter size
from the laid-in weights to the window's end, and in the reference at most
12 bytes a parameter beside a block's inputs. The reference that keeps
everything on the device, as it stood until PR 33, is the oracle here."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_run import DATA, new_family, run  # noqa: F401  (new_family is a fixture)

from benchmarks.lib import harness, refrun, traffic as traffic_lib
from benchmarks.reference import common, gpt2, vgg16


def check_held(result):
    held = result["info"]["held"]
    assert result["correct"] is True
    foreign = held["live_bytes_at_open"] - held["state_bytes_at_open"]
    assert 0 <= foreign < held["params_bytes"] <= held["state_bytes_at_open"], held
    return held


@pytest.mark.parametrize("workload", ["lm_tiny", "vgg_tiny", "lm_tiny_dp4"])
def test_the_window_opens_on_the_programs_state_and_nothing_of_its_size(workload):
    held = check_held(run(workload))
    assert held["reference_bytes_in_use_max"] is None or held["reference_bytes_in_use_max"] > 0


def test_the_window_opens_on_a_new_familys_state_alone(new_family):  # noqa: F811
    bench_file, extra, _ = new_family
    check_held(harness.run_cell("mlp_tiny", 2**31 + 29, 0.5, False, require_tpu=False,
                                bench_file=bench_file, data_dirs=[extra, DATA]))


# -- the reference alone -------------------------------------------------------

CASES = {"lm": ("lm-tiny", "tiny_t128_b8", gpt2), "lm_dp4": ("lm-tiny", "tiny_t128_b8_dp4", gpt2),
         "vgg": ("vgg-tiny", "tiny_img_b16", vgg16)}


def load(case):
    config, mix, ref = CASES[case]
    cfg = json.load(open(os.path.join(DATA, "configs", config + ".json")))
    traffic = json.load(open(os.path.join(DATA, "traffic", mix + ".json")))
    data = traffic_lib.make_data(cfg, traffic, 5)
    if "windows" in data:
        image, label = data["windows"][:, :-1], data["windows"][:, 1:]
    else:
        image, label = data["images"], data["labels"]
    rows = traffic["global_batch"]
    batches = [{"image": image[i * rows:(i + 1) * rows], "label": label[i * rows:(i + 1) * rows]}
               for i in range(traffic["check_steps"])]
    return ref, cfg, traffic, batches, jax.devices()[:traffic["chips"]]


@pytest.mark.parametrize("case", list(CASES))
def test_the_reference_holds_at_most_twelve_bytes_a_parameter(case):
    ref, cfg, traffic, batches, devices = load(case)
    shapes = jax.eval_shape(lambda key: ref.init_params(cfg, traffic, key), jax.random.key(0))
    tree = 4 * sum(x.size for x in shapes.values())
    block_rows = traffic["reference_block_rows"]  # a chip's share of a call's rows, whatever the chips
    block = sum(v[:block_rows].nbytes for v in batches[0].values())
    before = harness.device_bytes(jax.live_arrays(), devices[0])
    seen = []
    refrun.run_reference(ref, cfg, traffic, 7, batches, devices=devices,
                         watch=lambda: seen.append(harness.device_bytes(jax.live_arrays(), devices[0]) - before))
    assert len(seen) > 2 * len(shapes)  # every block and every leaf's update was looked at
    assert 2 * tree <= max(seen) <= 3 * tree + block + 4096, (max(seen), tree, block)


def oracle(ref, cfg, traffic, params, batches, *, control=None, keep_rows=None, devices=None):
    """``refrun.run_reference`` as it was: the start, a copy of it, both
    moments and the gradient sum all on the device."""
    opt = cfg["optimizer"]
    block_rows = traffic["reference_block_rows"]
    leaves = common.leaves_view(ref)
    by_row = None
    if devices is not None and len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
        by_row = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("rows"))
        params = jax.device_put(params, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        block_rows *= len(devices)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(p, gsum, lsum, block):
        loss, grads = jax.value_and_grad(ref.loss_sum)(p, block, cfg, control)
        return jax.tree.map(jnp.add, gsum, grads), lsum + loss

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 2))
    def update(p, grads, state, step, lr):
        return common.optimizer_update(p, grads, state, opt, step, lr)

    start, params = params, jax.tree.map(jnp.copy, params)
    state = common.optimizer_init(params, opt)
    losses, grad1 = [], None
    for step, batch in enumerate(batches):
        rows = len(batch["label"]) if keep_rows is None else keep_rows
        gsum = jax.tree.map(jnp.zeros_like, params)
        lsum = jnp.zeros((), jnp.float32)
        for lo in range(0, rows, block_rows):
            block = {k: v[lo:min(lo + block_rows, rows)] for k, v in batch.items()}
            block = {k: jnp.asarray(v) if by_row is None else jax.device_put(v, by_row) for k, v in block.items()}
            gsum, lsum = accumulate(params, gsum, lsum, block)
        grads = jax.tree.map(lambda g: g / rows, gsum)
        losses.append(float(lsum) / rows)
        if grad1 is None:
            grad1 = refrun._leaf_norms(leaves(grads, cfg))
        lr = common.schedule_lr(opt, step, traffic["steps_per_epoch"])
        params, state = update(params, grads, state, step, lr)
    delta = jax.tree.map(jnp.subtract, params, start)
    return {"losses": losses, "grad1": grad1, "moment": refrun._leaf_norms(leaves(state["m"], cfg)),
            "delta": refrun._leaf_norms(leaves(delta, cfg))}


def assert_same(got, want, rel=1e-6):
    assert got["losses"] == pytest.approx(want["losses"], rel=rel)
    for key in ("grad1", "moment", "delta"):
        assert got[key].keys() == want[key].keys()
        for leaf, value in want[key].items():
            # a leaf with no gradient (a key's bias) moves by round-off alone: nought against the median leaf
            assert got[key][leaf] == pytest.approx(value, rel=rel, abs=rel * float(np.median(list(want[key].values())))), (key, leaf)


@pytest.mark.parametrize("case,kwargs", [
    ("lm", {}), ("lm", {"control": "bf16"}), ("lm", {"keep_rows": 8}),
    ("lm_dp4", {}), ("lm_dp4", {"control": "bf16"}), ("lm_dp4", {"keep_rows": 4}),
    ("vgg", {}), ("vgg", {"control": "bf16"}), ("vgg", {"keep_rows": 16}),
])
def test_the_reference_reads_what_the_all_on_device_one_read(case, kwargs):
    ref, cfg, traffic, batches, devices = load(case)
    params = jax.jit(lambda key: ref.init_params(cfg, traffic, key))(jax.random.key(7))
    want = oracle(ref, cfg, traffic, params, batches, devices=devices, **kwargs)
    assert_same(refrun.run_reference(ref, cfg, traffic, 7, batches, devices=devices, **kwargs), want)


def test_the_comparison_with_the_oracle_sees_bfloat16_operands():
    ref, cfg, traffic, batches, devices = load("lm")
    params = jax.jit(lambda key: ref.init_params(cfg, traffic, key))(jax.random.key(7))
    rounded = oracle(ref, cfg, traffic, params, batches, devices=devices, control="bf16")
    with pytest.raises(AssertionError):
        assert_same(refrun.run_reference(ref, cfg, traffic, 7, batches, devices=devices), rounded)
