"""Expert parallelism (parallel/moe.py): routing parity, capacity dropping,
expert-sharded execution under jit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.ops import dispatch
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


# -- HeldExpertsMlp: dropless routing over a chip's share of the experts --------
#
# Against ``benchmarks/reference/nemotron_h.py``'s layer (a masked dense walk over
# the held experts, no sort and no buffer), float32, 8 published experts top-3.
# Tolerance: the same sums in another order; the worst case below (the loss,
# the tokens' gradient or a leaf's) read 9.9e-7 relative, HELD_GAP is five times that.

HELD_GAP = 5e-6
HELD = dict(expert_width=24, shared_width=40, experts_published=8, top_k=3, routed_scaling=2.5)


def held_layer(first, count):
    from distributed_training_pytorch_tpu.parallel.moe import HeldExpertsMlp

    return HeldExpertsMlp(held_first=first, held_count=count, **HELD)


def held_cfg(first, count):
    """The reference's keys for the same layer."""
    return {"n_routed_experts": count, "experts_held_first": first, "published": {"n_routed_experts": 8},
            "num_experts_per_tok": 3, "routed_scaling_factor": 2.5, "hidden_size": 16, "head_dim": 4,
            "mamba_num_heads": 1, "mamba_head_dim": 1, "ssm_state_size": 1, "n_groups": 1, "num_attention_heads": 1,
            "num_key_value_heads": 1}


def whole_layer_params(seed, d=16):
    """All 8 experts' weights under the reference's names, a non-zero selection bias among them
    (the router's scaled so that its scores spread at any ``d`` as they do at 16)."""
    k = jax.random.split(jax.random.key(seed), 6)
    return {"mixer.gate.w": (16 / d) ** 0.5 * jax.random.normal(k[0], (8, d)), "mixer.gate.e_score_correction_bias": 0.3 * jax.random.normal(k[1], (8,)),
            "mixer.experts.up_proj.w": 0.3 * jax.random.normal(k[2], (8, d, 24)),
            "mixer.experts.down_proj.w": 0.3 * jax.random.normal(k[3], (8, 24, d)),
            "mixer.shared_experts.up_proj.w": 0.3 * jax.random.normal(k[4], (d, 40)),
            "mixer.shared_experts.down_proj.w": 0.3 * jax.random.normal(k[5], (40, d))}


def share_of(p, first, count):
    """(the reference's parameters, the program's) for experts ``first … first + count − 1``."""
    held = dict(p, **{k: p[k][first:first + count] for k in ("mixer.experts.up_proj.w", "mixer.experts.down_proj.w")})
    program = {"router": p["mixer.gate.w"], "score_correction_bias": p["mixer.gate.e_score_correction_bias"],
               "experts_up": held["mixer.experts.up_proj.w"], "experts_down": held["mixer.experts.down_proj.w"],
               "shared_up": {"kernel": p["mixer.shared_experts.up_proj.w"]}, "shared_down": {"kernel": p["mixer.shared_experts.down_proj.w"]}}
    return held, {"params": program}


def reference_layer(x, p, first, count):
    from benchmarks.reference import nemotron_h as ref

    with jax.default_matmul_precision("highest"):
        return ref._moe(x, p, held_cfg(first, count), lambda v: v)


def gap(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(params=[("gather", 16, 19), ("pallas", 128, 20)], ids=["gather", "pallas"])
def rows(request, monkeypatch):
    """``(d, tokens a batch row)`` for a layer whose four row movements take
    their ``jax.numpy`` form (what the CPU runs), or the kernels of
    ``ops/moe_rows.py`` interpreted, at a width and a token count that tile.
    The program has no knob for it: the test widens the platforms the kernels
    are the default on."""
    form, d, t = request.param
    if form == "pallas":
        monkeypatch.setattr(dispatch, "MOE_ROWS_BACKENDS", (jax.default_backend(),))
    yield d, t
    assert ("moe", "moe_rows", form) in {(r["model"], r["op"], r["path"]) for r in dispatch.records()}


@pytest.mark.parametrize("first,count", [(0, 4), (4, 4), (2, 3), (0, 8)])
def test_held_experts_match_the_reference_layer_and_its_gradients(first, count, rows):
    d, t = rows
    x = jax.random.normal(jax.random.key(7), (2, t, d))
    ref_p, variables = share_of(whole_layer_params(first * 10 + count, d), first, count)
    weights = jax.random.normal(jax.random.key(8), x.shape)
    got, grads = jax.value_and_grad(lambda v, x: jnp.sum(weights * held_layer(first, count).apply(v, x)), argnums=(0, 1))(variables, x)
    want, want_grads = jax.value_and_grad(lambda p, x: jnp.sum(weights * reference_layer(x, p, first, count)), argnums=(0, 1))(ref_p, x)
    assert abs(float(got) - float(want)) <= HELD_GAP * abs(float(want))
    assert gap(grads[1], want_grads[1]) <= HELD_GAP  # the tokens'
    _, back = share_of(want_grads[0], 0, count)  # the reference's gradients in the program's tree (already the share's)
    gaps = jax.tree.map(gap, {k: v for k, v in grads[0]["params"].items() if k != "score_correction_bias"},
                        {k: v for k, v in back["params"].items() if k != "score_correction_bias"})
    assert max(jax.tree.leaves(gaps)) <= HELD_GAP, gaps
    assert float(jnp.abs(grads[0]["params"]["score_correction_bias"]).max()) == 0  # it selects, no more


@pytest.mark.parametrize("shares", [[(0, 4), (4, 4)], [(0, 2), (2, 2), (4, 2), (6, 2)], [(0, 3), (3, 5)]])
def test_the_shares_add_up_to_the_uncut_layer(shares, rows):
    """Each share's layer gives its own experts' part of every token's sum
    plus the shared expert, which every chip computes alike: the shares' sum,
    with the shared expert counted once, is the whole layer as the reference
    computes it with all 8 experts held."""
    d, t = rows
    p = whole_layer_params(3, d)
    x = jax.random.normal(jax.random.key(5), (2, t, d))
    whole = reference_layer(x, p, 0, 8)
    shared_only = reference_layer(x, dict(p, **{"mixer.experts.up_proj.w": p["mixer.experts.up_proj.w"][:0],
                                                "mixer.experts.down_proj.w": p["mixer.experts.down_proj.w"][:0]}), 0, 0)
    parts = [held_layer(first, count).apply(share_of(p, first, count)[1], x) for first, count in shares]
    total = sum(parts) - (len(shares) - 1) * shared_only
    assert gap(total, whole) <= HELD_GAP
    assert all(gap(part, whole) > 1e-2 for part in parts)  # and no one share is the whole


@pytest.mark.parametrize("case", ["all_to_one_held_expert", "none_here", "all_here"])
def test_no_pair_is_dropped_at_any_imbalance(case, rows):
    """The worst the router can do: every token's first choice one held
    expert (its buffer rows are then all live for that expert: 38 of 38 tokens,
    where a capacity factor of 1.25 would keep 18), every pair routed to
    experts held elsewhere, and every pair routed here."""
    d, t = rows
    n = 2.0 * t
    p = whole_layer_params(11, d)
    bias = {"all_to_one_held_expert": jnp.zeros(8).at[1].set(50.0),  # expert 1 is everyone's first choice
            "none_here": jnp.zeros(8).at[4:7].set(50.0),  # experts 4, 5, 6 take every pair
            "all_here": jnp.zeros(8).at[:3].set(50.0)}[case]  # experts 0, 1, 2 take every pair
    p["mixer.gate.e_score_correction_bias"] = bias
    x = jax.random.normal(jax.random.key(2), (2, t, d))
    ref_p, variables = share_of(p, 0, 4)
    out, inter = held_layer(0, 4).apply(variables, x, mutable=["intermediates"])
    assert gap(out, reference_layer(x, ref_p, 0, 4)) <= HELD_GAP
    pairs, fullest = (float(inter["intermediates"][k][0]) for k in ("moe_pairs_local", "moe_pairs_max_expert"))
    assert (pairs, fullest) == {"all_to_one_held_expert": (pairs, n), "none_here": (0.0, 0.0), "all_here": (3 * n, n)}[case]
    assert case != "all_to_one_held_expert" or n <= pairs <= 3 * n


def test_the_selection_bias_changes_who_is_chosen_and_not_the_weights():
    from benchmarks.reference import nemotron_h as ref

    p = whole_layer_params(4)
    x = jax.random.normal(jax.random.key(1), (1, 64, 16))
    cfg = held_cfg(0, 8)
    top, weights = ref.routing(x, p, cfg)
    top0, weights0 = ref.routing(x, dict(p, **{"mixer.gate.e_score_correction_bias": jnp.zeros(8)}), cfg)
    assert float(jnp.mean(jnp.sort(top, -1) != jnp.sort(top0, -1))) > 0.05  # other experts are chosen
    scores = jax.nn.sigmoid(x @ p["mixer.gate.w"].T)
    chosen = jnp.take_along_axis(scores, top, -1)  # and their weights are the unbiased scores', normalised and scaled
    np.testing.assert_allclose(np.asarray(weights), np.asarray(2.5 * chosen / chosen.sum(-1, keepdims=True)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-5)
    # the program's layer with the bias is the reference with it, and differs from the layer without
    ref_p, variables = share_of(p, 0, 8)
    out = held_layer(0, 8).apply(variables, x)
    assert gap(out, reference_layer(x, ref_p, 0, 8)) <= HELD_GAP
    no_bias = jax.tree.map(lambda v: v, variables)
    no_bias["params"]["score_correction_bias"] = jnp.zeros(8)
    assert gap(held_layer(0, 8).apply(no_bias, x), out) > 1e-2


def test_held_rows_is_a_stable_counting_sort():
    top = jnp.asarray([[5, 2, 9], [2, 3, 2], [7, 3, 8], [3, 2, 0]])  # experts 2 and 3 are held
    dest, live, src, sizes = moe_lib.held_rows(top, 2, 2)
    assert sizes.tolist() == [4, 3]
    assert live.tolist() == [[False, True, False], [True, True, True], [False, True, False], [True, True, False]]
    # expert 2's pairs first, in token order, then expert 3's: rows 0-3 and 4-6 of a 12-row buffer
    assert src[:7].tolist() == [1, 3, 5, 10, 4, 7, 9]
    assert [int(dest.reshape(-1)[pair]) for pair in src[:7].tolist()] == list(range(7))
    assert int(jnp.sum(jnp.where(live, 0, dest))) == 0  # a pair held elsewhere points at row 0 and is masked


def test_held_experts_outside_the_published_range_are_refused():
    with pytest.raises(ValueError, match="not among"):
        held_layer(6, 4).init(jax.random.key(0), jnp.zeros((1, 4, 16)))


# -- the row movements' kernels (ops/moe_rows.py), interpreted, against their jax.numpy form --------
#
# 64 tokens of width 128, top-3 over 8 published experts of which 4 are held: a
# buffer of 192 rows in tiles of 64, 64 tokens in one tile. The kernels add a
# token's pairs in the order j = 0 … k−1; the jax.numpy form's sum over the k
# axis is the compiler's to order, so sums are held to float32 round-off and
# what is only moved or scaled to the bit.

ROWS_N, ROWS_K, ROWS_D, ROWS_HELD = 64, 3, 128, 4


def routed(share):
    """``top`` ``[64, 3]`` for a share of the pairs held here (experts 0-3 of 8)."""
    k = jax.random.split(jax.random.key(17), 3)
    here = jnp.argsort(jax.random.uniform(k[0], (ROWS_N, ROWS_HELD)), axis=1)[:, :ROWS_K]  # a token's experts differ
    elsewhere = ROWS_HELD + here[:, ::-1]
    if share == "one_expert":  # every token's first choice is expert 1, its others are held elsewhere
        return elsewhere.at[:, 0].set(1)
    return jnp.where(jax.random.uniform(k[2], (ROWS_N, ROWS_K)) < share, here, elsewhere)


SHARES = [pytest.param(0.0, id="none_here"), pytest.param(0.03, id="the_windows_3pct"), pytest.param(0.5, id="even"),
          pytest.param("one_expert", id="one_expert_takes_every_token"), pytest.param(1.0, id="every_pair_here")]


def route_of(top, tile):
    from distributed_training_pytorch_tpu.ops import moe_rows

    dest, live, src, sizes = moe_lib.held_rows(top, 0, ROWS_HELD)
    return moe_lib.Route(dest, live, src, jnp.sum(sizes), *moe_rows.live_pairs(live, tile))


def live_rows(route, rows):
    """What a row past the live ones holds is no one's: zeros, for a comparison."""
    return jnp.where((jnp.arange(rows.shape[0]) < route.n_live)[:, None], rows, 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("share", SHARES)
def test_each_row_movement_and_its_transpose_equal_the_jax_numpy_form(share, dtype):
    tile = 64
    route = route_of(routed(share), tile)
    k = jax.random.split(jax.random.key(23), 5)
    x, rows, d_rows = (jax.random.normal(key, shape).astype(dtype) for key, shape in
                       zip(k[:3], ((ROWS_N, ROWS_D), (ROWS_N * ROWS_K, ROWS_D), (ROWS_N * ROWS_K, ROWS_D))))
    weights, d_out = jax.random.uniform(k[3], (ROWS_N, ROWS_K)), jax.random.normal(k[4], (ROWS_N, ROWS_D))

    def movements(t):
        into, back = jax.vjp(lambda x: moe_lib._rows_in(t, x, route), x)
        out, back_out = jax.vjp(lambda rows, weights: moe_lib._rows_out(t, rows, weights, route), rows, weights)
        (dx,), (d_buffer, d_weights) = back(d_rows), back_out(d_out)
        return {"dispatch": live_rows(route, into), "dx": dx, "combine": out, "d_rows": live_rows(route, d_buffer), "d_weights": d_weights}

    got, want = movements(tile), movements(None)
    for name in want:
        assert got[name].dtype == want[name].dtype and got[name].shape == want[name].shape, name
        a, b = np.asarray(got[name], np.float32), np.asarray(want[name], np.float32)
        if name in ("dispatch", "d_rows"):  # a row chosen, or one product a row: nothing to reorder
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:  # float32 sums (a token's k rows, a row's d products) in another order; `dx` then rounded to `dtype` once
            np.testing.assert_allclose(a, b, rtol=2.0**-7 if got[name].dtype == jnp.bfloat16 else 1e-5, atol=1e-5, err_msg=name)
    assert float(jnp.abs(want["combine"]).sum()) > 0 or share == 0.0


@pytest.mark.parametrize("share", SHARES)
def test_the_buffers_side_visits_the_live_tiles_and_no_other(share):
    """A tile the kernel visits is written (rows, then zeros to its end); one it
    does not keeps what the allocation held, here NaN: the tiles written are
    ``ceil(n_live / tile)`` (one where nothing is live: the buffer's first rows
    are then zeros), whatever the buffer's size."""
    from distributed_training_pytorch_tpu.ops import moe_rows

    tile = 16
    route = route_of(routed(share), 64)
    n_live = int(route.n_live)
    x = jax.random.normal(jax.random.key(1), (ROWS_N, ROWS_D))
    d_out, weights = jax.random.normal(jax.random.key(2), (ROWS_N, ROWS_D)), jax.random.uniform(jax.random.key(3), (ROWS_N, ROWS_K))
    nan = jnp.full((ROWS_N * ROWS_K, ROWS_D), jnp.nan)
    tokens = route.src // ROWS_K
    plain = moe_rows.rows_from_table(x, route.src, route.n_live, fill=nan, k=ROWS_K, tile=tile)
    scaled, dots = moe_rows.rows_from_table(d_out, route.src, route.n_live, weights, plain, fill=nan, k=ROWS_K, tile=tile)
    for out in (plain, scaled):
        written = ~np.isnan(np.asarray(out)).all(axis=1).reshape(-1, tile)
        assert written.all(axis=1).sum() == written.any(axis=1).sum() == max(-(-n_live // tile), 1)  # whole tiles, from the front
        assert not np.isnan(np.asarray(out)[:n_live]).any() and (np.asarray(out)[n_live:written.sum()] == 0).all()
    np.testing.assert_array_equal(np.asarray(plain[:n_live]), np.asarray(x[tokens][:n_live]))
    # a live row's product sits at its pair, and every other pair reads zero (the rows past the live ones are NaN here)
    want = jnp.where(route.live, jnp.sum(x[:, None, :] * d_out[:, None, :], -1), 0)
    np.testing.assert_allclose(np.asarray(dots), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_rows_the_kernels_never_write_reach_no_output_and_no_gradient(dtype, monkeypatch):
    """The whole layer, kernels forced, with every row of the buffer that the
    buffer's side leaves unwritten poisoned with NaN (in a training step they
    hold the allocation's leftovers): the grouped products, relu² and every
    transpose between the two movements run over them, and the loss and all
    gradients are finite and those of the ``jax.numpy`` form."""
    from distributed_training_pytorch_tpu.ops import moe_rows
    from distributed_training_pytorch_tpu.parallel.moe import HeldExpertsMlp

    layer = HeldExpertsMlp(held_first=0, held_count=2, dtype=dtype, **HELD)  # 2 of 8 held: most of the buffer is dead
    x = jax.random.normal(jax.random.key(1), (2, 64, 128))
    variables = jax.tree.map(lambda v: 4 * v, layer.init(jax.random.key(0), x))
    cot = jax.random.normal(jax.random.key(2), x.shape)
    both = jax.value_and_grad(lambda v, x: jnp.sum(cot * layer.apply(v, x).astype(jnp.float32)), argnums=(0, 1))
    want = both(variables, x)
    clean, seen = moe_rows.rows_from_table, []

    def poisoned(table, src, n_live, weights=None, dot_with=None, fill=None, *, out_dtype=None, **kw):
        out_dtype = out_dtype or table.dtype
        seen.append(int(src.shape[0]) - int(n_live))
        return clean(table, src, n_live, weights, dot_with, jnp.full((src.shape[0], table.shape[1]), jnp.nan, out_dtype), out_dtype=out_dtype, **kw)

    monkeypatch.setattr(dispatch, "MOE_ROWS_BACKENDS", (jax.default_backend(),))
    monkeypatch.setattr(moe_rows, "rows_from_table", poisoned)
    got = both(variables, x)
    assert len(seen) == 2 and min(seen) > 2 * 128  # dispatch and the combine's transpose, each with dead tiles behind the live ones
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tokens,width,dtype,why", [(64, 100, jnp.float32, "no multiple of 128"), (60, 128, jnp.float32, "no multiple of 8"),
                                                    (64, 128, jnp.float16, "neither float32 nor bfloat16")])
def test_a_shape_the_row_kernels_do_not_take_keeps_the_jax_numpy_form(tokens, width, dtype, why, monkeypatch):
    monkeypatch.setattr(dispatch, "MOE_ROWS_BACKENDS", (jax.default_backend(),))
    assert dispatch.moe_rows_tile("refused", tokens, 3, width, dtype) is None
    assert why in [r["reason"] for r in dispatch.records() if r["model"] == "refused" and r["path"] == "gather"][-1]
    assert dispatch.moe_rows_tile("taken", 64, 3, 128, jnp.bfloat16) == 64
