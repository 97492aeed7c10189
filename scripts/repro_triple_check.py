"""Minimal repro of the upstream XLA SPMD-partitioner CHECK that blocks the
GSPMD-constraint formulation of the data x expert x pipe composition
(bisected on jax 0.9 / CPU).

An MoE stage whose expert parallelism is expressed as sharding CONSTRAINTS
(parallel.moe.MoEMlp weight constraints) inside pipeline_apply's pipe-manual
shard_map region dies with a process-fatal

    F spmd_partitioner_util.cc:495 Check failed:
      partition_group_list.num_replica_groups() *
      partition_group_list.num_devices_per_group() ==
      device_groups.num_devices_per_group()
    ... ExpandDeviceGroupsWithIota / AllReduceAlongShardingDims

This is why the supported triple path is MANUAL expert parallelism instead:
pipeline_apply(extra_manual_axes=("expert",), stage_param_specs=...) with
moe.manual_expert_ffn_local stage bodies (see tests/test_pipeline.py
test_pipeline_triple_data_expert_pipe). Nested shard_map is not an option
either: Shardy rejects both re-binding a parent's manual axis and an inner
mesh that differs from the context mesh (errors quoted in
moe.manual_expert_mlp).

Run me to confirm the upstream bug still exists (the process CRASHES when it
does — a clean exit 0 means a jax upgrade fixed it and the GSPMD formulation
can be re-evaluated):  python scripts/repro_triple_check.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_training_pytorch_tpu import compat  # noqa: E402

compat.force_host_devices(8)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.parallel.moe import MoEMlp
from distributed_training_pytorch_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_stage_params,
)

rng = np.random.RandomState(0)
mesh = mesh_lib.create_mesh(
    {mesh_lib.DATA_AXIS: 2, mesh_lib.PIPE_AXIS: 2, mesh_lib.EXPERT_AXIS: 2}
)
d, hid, pipe = 8, 16, 2
moe = MoEMlp(num_experts=2, hidden_dim=hid, top_k=2, capacity_factor=4.0, num_groups=2)
x0 = jnp.asarray(rng.randn(4, 8, d), jnp.float32)
micro = jnp.asarray(rng.randn(4, 4, 8, d), jnp.float32)
stages = [
    {
        "w1": jnp.asarray(rng.randn(d, hid) * 0.2, jnp.float32),
        "w2": jnp.asarray(rng.randn(hid, d) * 0.2, jnp.float32),
        "moe": moe.init(jax.random.key(30 + i), x0)["params"],
    }
    for i in range(pipe)
]


def stage(p, x):
    x = x + jax.nn.gelu(x @ p["w1"]) @ p["w2"]
    return x + moe.apply({"params": p["moe"]}, x)  # GSPMD expert constraints


stacked = stack_stage_params(stages)


def loss(stacked):
    fed = jax.lax.with_sharding_constraint(
        micro, PartitionSpec(None, mesh_lib.DATA_AXIS)
    )
    return jnp.sum(pipeline_apply(stacked, fed, stage, mesh) ** 2)


print("compiling the GSPMD-constraint triple (crashes while the bug exists)...")
with jax.sharding.set_mesh(mesh):
    l, _ = jax.jit(jax.value_and_grad(loss))(stacked)
print(f"NO CRASH (loss {float(l):.3f}) — the upstream CHECK is fixed; the "
      "GSPMD formulation of data x expert x pipe can be re-evaluated.")
