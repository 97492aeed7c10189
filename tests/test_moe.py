"""Expert parallelism (parallel/moe.py): routing parity, capacity dropping,
expert-sharded execution under jit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.parallel import moe as moe_lib
from distributed_training_pytorch_tpu.parallel.moe import EXPERT_AXIS, MoEMlp


def dense_reference(variables, x, top_k):
    """Per-token loop: top-k experts, renormalized gates, no capacity limit."""
    params = variables["params"]
    w_r, b_r = params["router"]["kernel"], params["router"]["bias"]
    w_in, w_out = params["w_in"], params["w_out"]
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = tokens @ np.asarray(w_r, np.float64) + np.asarray(b_r, np.float64)
    gates = np.exp(logits - logits.max(-1, keepdims=True))
    gates /= gates.sum(-1, keepdims=True)
    out = np.zeros_like(tokens)
    for si in range(tokens.shape[0]):
        top = np.argsort(-gates[si])[:top_k]
        norm = gates[si][top].sum()
        for ei in top:
            h = tokens[si] @ np.asarray(w_in[ei], np.float64)
            h = np.asarray(jax.nn.gelu(jnp.asarray(h)), np.float64)
            out[si] += (gates[si][ei] / norm) * (h @ np.asarray(w_out[ei], np.float64))
    return out.reshape(x.shape)


@pytest.mark.parametrize("top_k", [pytest.param(1, marks=pytest.mark.slow), 2])  # [1] out of tier-1 (PR 21)
def test_moe_matches_dense_reference(top_k):
    """With generous capacity nothing drops -> exact top-k mixture parity."""
    model = MoEMlp(num_experts=4, hidden_dim=16, top_k=top_k, capacity_factor=8.0)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 12, 8), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    out = model.apply(variables, x)
    ref = dense_reference(variables, x, top_k)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_moe_capacity_drops_deterministically():
    """capacity 1 with many tokens: per expert only the first token (in order)
    is served per choice; output is finite and some tokens are zero."""
    model = MoEMlp(num_experts=2, hidden_dim=8, top_k=1, capacity_factor=1e-9)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    out1 = model.apply(variables, x)
    out2 = model.apply(variables, x)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    out = np.asarray(out1).reshape(-1, 4)
    assert np.isfinite(out).all()
    assert (np.abs(out).sum(-1) == 0).any(), "capacity 1 must drop some tokens"
    assert (np.abs(out).sum(-1) > 0).any(), "but serve at least one"


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_moe_aux_losses_sown():
    model = MoEMlp(num_experts=4, hidden_dim=8, top_k=2)
    x = jnp.ones((1, 8, 4))
    variables = model.init(jax.random.key(0), x)
    _, state = model.apply(variables, x, mutable=["intermediates"])
    inter = state["intermediates"]
    (lb,) = inter["load_balance_loss"]
    (zl,) = inter["router_z_loss"]
    assert np.isfinite(float(lb)) and float(lb) >= 1.0 - 1e-6  # >= 1 by Cauchy-Schwarz
    assert np.isfinite(float(zl))


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_moe_expert_sharded_under_jit(devices):
    """data x expert mesh: expert-stacked params and buffers shard over the
    expert axis; jitted output matches the single-device result."""
    mesh = mesh_lib.create_mesh(
        {mesh_lib.DATA_AXIS: 2, EXPERT_AXIS: 4}, devices=devices
    )
    model = MoEMlp(num_experts=4, hidden_dim=16, top_k=2, capacity_factor=8.0)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 8, 8), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    expected = model.apply(variables, x)

    with jax.sharding.set_mesh(mesh):
        out = jax.jit(model.apply)(variables, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_moe_grouped_routing_matches_dense(devices):
    """num_groups > 1 (the at-scale layout): with generous per-group capacity
    nothing drops, so grouped routing still matches the dense mixture; and the
    grouped buffers run expert+data sharded under jit."""
    mesh = mesh_lib.create_mesh(
        {mesh_lib.DATA_AXIS: 2, EXPERT_AXIS: 4}, devices=devices
    )
    model = MoEMlp(num_experts=4, hidden_dim=16, top_k=2, capacity_factor=8.0, num_groups=2)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(4, 8, 8), jnp.float32)  # 32 tokens -> 2 groups of 16
    variables = model.init(jax.random.key(0), x)
    ref = dense_reference(variables, x, top_k=2)
    out = model.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4)
    with jax.sharding.set_mesh(mesh):
        out_sharded = jax.jit(model.apply)(variables, x)
    np.testing.assert_allclose(np.asarray(out_sharded), ref, atol=2e-4)


def test_moe_rejects_indivisible_groups():
    model = MoEMlp(num_experts=2, hidden_dim=4, num_groups=3)
    x = jnp.ones((1, 8, 4))  # 8 tokens, 3 groups
    with pytest.raises(ValueError, match="not divisible by num_groups"):
        model.init(jax.random.key(0), x)


def test_engine_establishes_ambient_mesh(devices):
    """Regression: TrainEngine must set the ambient mesh while tracing, or
    in-model with_sharding_constraint (bare PartitionSpecs, as MoE uses)
    silently no-ops on the production path."""
    import optax
    from flax import linen as nn

    from distributed_training_pytorch_tpu.train import TrainEngine

    mesh = mesh_lib.create_mesh(
        {mesh_lib.DATA_AXIS: 2, EXPERT_AXIS: 4}, devices=devices
    )
    seen = []

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x, *, train=False):
            seen.append(jax.sharding.get_abstract_mesh().axis_names)
            return nn.Dense(3)(x.reshape(x.shape[0], -1))

    model = Probe()

    def loss_fn(params, ms, batch, rng, train):
        logits = model.apply({"params": params}, batch["image"], train=train)
        loss = jnp.mean(logits**2)
        return loss, ({"loss": loss}, ms)

    engine = TrainEngine(loss_fn, optax.sgd(0.01), mesh)
    state = engine.init_state(
        jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 4)))
    )
    batch = engine.shard_batch({"image": np.zeros((8, 4), np.float32)})
    engine.train_step(state, batch)
    assert seen and all(EXPERT_AXIS in axes for axes in seen if axes), seen
    assert any(axes for axes in seen), "ambient mesh was never set during trace"


@pytest.mark.parametrize("top_k,num_groups", [(1, 1), (2, 1), (2, 2)])
@pytest.mark.slow
def test_moe_sort_dispatch_matches_einsum(top_k, num_groups):
    """The argsort/scatter dispatch is semantics-identical to the GShard
    one-hot path: same outputs AND same grads, including under capacity
    pressure (drops follow the same choice-major priority order)."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, 16, 8), jnp.float32)
    for cap in (8.0, 0.6):  # generous and dropping
        kw = dict(
            num_experts=4, hidden_dim=16, top_k=top_k,
            capacity_factor=cap, num_groups=num_groups,
        )
        m_ein = MoEMlp(dispatch_impl="einsum", **kw)
        m_sort = MoEMlp(dispatch_impl="sort", **kw)
        variables = m_ein.init(jax.random.key(1), x)
        out_ein = m_ein.apply(variables, x)
        out_sort = m_sort.apply(variables, x)
        np.testing.assert_allclose(
            np.asarray(out_ein), np.asarray(out_sort), atol=2e-5,
            err_msg=f"cap={cap}",
        )

        def loss(v, m):
            return jnp.sum(m.apply(v, x) ** 2)

        g_ein = jax.grad(loss)(variables, m_ein)
        g_sort = jax.grad(loss)(variables, m_sort)
        for a, b in zip(jax.tree.leaves(g_ein), jax.tree.leaves(g_sort), strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_moe_sort_dispatch_sharded_under_jit(devices):
    mesh = mesh_lib.create_mesh(
        {mesh_lib.DATA_AXIS: 2, EXPERT_AXIS: 4}, devices=devices
    )
    model = MoEMlp(
        num_experts=4, hidden_dim=16, top_k=2, capacity_factor=8.0,
        num_groups=2, dispatch_impl="sort",
    )
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(4, 8, 8), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    expected = dense_reference(variables, x, top_k=2)
    with jax.sharding.set_mesh(mesh):
        out = jax.jit(model.apply)(variables, x)
    np.testing.assert_allclose(np.asarray(out), expected, atol=2e-4)


def test_moe_decode_capacity_free_matches_dense():
    """decode=True routes every token to its full top-k (no capacity, no
    drops) — exactly the dense per-token mixture, with the same parameters
    the capacity-routed training path uses."""
    model = MoEMlp(num_experts=4, hidden_dim=16, top_k=2, capacity_factor=1e-9)
    rng = np.random.RandomState(9)
    # decode: T=1 tokens; 8 of them so the starved training path (capacity 1,
    # 16 choice-entries for 4 slots) provably zeroes some tokens entirely
    x = jnp.asarray(rng.randn(8, 1, 8), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    out = model.apply(variables, x, decode=True)
    ref = dense_reference(variables, x, top_k=2)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4)
    # Training path under the same starved capacity drops tokens; decode
    # must not (that is the point of the capacity-free router).
    out_train = np.asarray(model.apply(variables, x)).reshape(-1, 8)
    assert (np.abs(out_train).sum(-1) == 0).any()
    assert (np.abs(np.asarray(out).reshape(-1, 8)).sum(-1) > 0).all()


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
@pytest.mark.parametrize(
    "tokens,expected_impl",
    [(16, "einsum"), (moe_lib.SORT_DISPATCH_MIN_GROUP, "sort")],
)
def test_moe_auto_dispatch_selects_by_group_size(tokens, expected_impl, monkeypatch):
    """dispatch_impl='auto' (the default) resolves from the static group size
    at the measured ~4k crossover — and produces the same numbers as the impl
    it selects."""
    seen = []
    orig_vmap = jax.vmap

    def spy_vmap(fn, *a, **kw):
        if getattr(fn, "__name__", "") in ("route", "route_sort"):
            seen.append(fn.__name__)
        return orig_vmap(fn, *a, **kw)

    monkeypatch.setattr(moe_lib.jax, "vmap", spy_vmap)
    kw = dict(num_experts=4, hidden_dim=8, top_k=2, capacity_factor=2.0)
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(1, tokens, 8), jnp.float32)
    auto = MoEMlp(dispatch_impl="auto", **kw)
    variables = auto.init(jax.random.key(2), x)
    seen.clear()
    out_auto = auto.apply(variables, x)
    assert seen == [{"einsum": "route", "sort": "route_sort"}[expected_impl]]
    out_explicit = MoEMlp(dispatch_impl=expected_impl, **kw).apply(variables, x)
    np.testing.assert_allclose(
        np.asarray(out_auto), np.asarray(out_explicit), atol=2e-5
    )


def test_moe_rejects_unknown_dispatch_impl():
    model = MoEMlp(num_experts=2, hidden_dim=4, dispatch_impl="hash")
    with pytest.raises(ValueError, match="dispatch_impl"):
        model.init(jax.random.key(0), jnp.ones((1, 4, 4)))


@pytest.mark.slow  # soak-shaped: moved out of tier-1 to keep it inside its cap (PR 21)
def test_manual_expert_mlp_matches_gspmd_path(devices):
    """manual_expert_mlp (nested-shard_map manual expert parallelism): both
    exchange formulations match the GSPMD-constraint MoEMlp forward AND
    gradient on a data x expert mesh."""
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
    from distributed_training_pytorch_tpu.parallel.moe import manual_expert_mlp

    rng = np.random.RandomState(0)
    kw = dict(num_experts=4, hidden_dim=16, top_k=2, capacity_factor=2.0, num_groups=4)
    moe = MoEMlp(dispatch_impl="einsum", **kw)
    x = jnp.asarray(rng.randn(4, 8, 8), jnp.float32)
    variables = moe.init(jax.random.key(1), x)
    ref = moe.apply(variables, x)
    g_ref = jax.grad(lambda p: jnp.sum(moe.apply({"params": p}, x) ** 2))(
        variables["params"]
    )

    mesh = mesh_lib.create_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.EXPERT_AXIS: 2}, devices=devices[:4]
    )
    for exchange in ("all_to_all", "psum"):
        def fwd(p, x, exchange=exchange):
            return manual_expert_mlp(
                p, x, num_experts=4, top_k=2, capacity_factor=2.0,
                num_groups=4, mesh=mesh, exchange=exchange,
            )

        with jax.sharding.set_mesh(mesh):
            got = jax.jit(fwd)(variables["params"], x)
            g_man = jax.jit(jax.grad(lambda p: jnp.sum(fwd(p, x) ** 2)))(
                variables["params"]
            )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_man), strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_manual_expert_mlp_rejects_nesting(devices):
    """Inside an enclosing manual region the GSPMD/nested paths are both
    unusable (Shardy rejections quoted in the docstring) — the error must
    point at the supported workaround, not die in the lowering."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from jax.sharding import set_mesh
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
    from distributed_training_pytorch_tpu.parallel.moe import manual_expert_mlp

    mesh = mesh_lib.create_mesh(
        {mesh_lib.PIPE_AXIS: 2, mesh_lib.EXPERT_AXIS: 2}, devices=devices[:4]
    )
    rng = np.random.RandomState(0)
    moe = MoEMlp(num_experts=2, hidden_dim=8, top_k=1, num_groups=2)
    x = jnp.asarray(rng.randn(2, 4, 8), jnp.float32)
    params = moe.init(jax.random.key(0), x)["params"]

    def outer(x):
        return manual_expert_mlp(
            params, x, num_experts=2, top_k=1, num_groups=2, mesh=mesh
        )

    with pytest.raises(ValueError, match="extra_manual_axes"):
        with set_mesh(mesh):
            jax.jit(
                shard_map(
                    outer, mesh=mesh, in_specs=P(), out_specs=P(),
                    axis_names=frozenset({mesh_lib.PIPE_AXIS}),
                )
            )(x)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_manual_expert_mlp_degenerate_mesh(devices):
    """On a mesh without an expert axis the specs reference only present
    axes and the collectives compile out — exact parity with plain apply."""
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
    from distributed_training_pytorch_tpu.parallel.moe import manual_expert_mlp

    rng = np.random.RandomState(3)
    moe = MoEMlp(num_experts=2, hidden_dim=8, top_k=1, num_groups=2,
                 dispatch_impl="einsum")
    x = jnp.asarray(rng.randn(2, 4, 8), jnp.float32)
    v = moe.init(jax.random.key(0), x)
    mesh = mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 2}, devices=devices[:2])
    with jax.sharding.set_mesh(mesh):
        got = jax.jit(
            lambda p, x: manual_expert_mlp(
                p, x, num_experts=2, top_k=1, num_groups=2, mesh=mesh
            )
        )(v["params"], x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(moe.apply(v, x)), atol=1e-6)
