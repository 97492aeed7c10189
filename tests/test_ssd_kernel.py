"""The Mamba-2 scan's Pallas kernels (``ops/ssd.py:ssd_scan``, ``ssd_fwd`` /
``ssd_bwd`` with their written backward), interpreted on the CPU, against the
sequential float32 recurrence (``benchmarks/reference/granite_hybrid.py``) and
against the ``jax.numpy`` chunked form they replace on the chip.

Shapes: the smallest the kernels take (``ssd_tiles``): chunk 128, 128 states,
heads of 64 channels as at the published widths. 32 heads are two head blocks
of 16; 8 heads one of 8.

Tolerances. In float32 the kernels compute the chunked form's terms in
another order (a head block's partial sums of ``dB`` / ``dC``, the state as
``[H·P, N]``, ``d cs`` summed in the kernel): ``y`` is the chunked form's to the
last bit, and over the cases below the worst input's gradient read 6.2e-7 to
3.4e-6 against the recurrence and 1.1e-7 to 3.1e-6 against the chunked form,
which itself sits 6.2e-7 to 2.2e-6 from the recurrence: all under the
FLOAT32_GAP of ``tests/test_hybrid_lm.py`` (1e-5). With bfloat16 operands both
forms round ``L ⊙ C Bᵀ``, ``Δ ⊙ x``, ``B``, ``C`` and the entering state at the
same places, so ``y`` is the chunked form's to 5e-8; the gradients differ by
the backward's own roundings (the kernels round ``dy`` and the tiles' gradients
once where jax's transposes round them at each operation): 0.8e-3 to 3.9e-3
over two seeds of each case, BFLOAT16_GAP two and a half times that, and each
of the two forms sits 1.4e-3 to 3.3e-3 from the float32 recurrence, where the
float32 tolerance would put it a hundred times closer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_hybrid as ref
from distributed_training_pytorch_tpu.models import HybridConfig, HybridLM
from distributed_training_pytorch_tpu.ops import dispatch
from distributed_training_pytorch_tpu.ops.ssd import ssd_chunked, ssd_scan, ssd_tiles

from test_hybrid_lm import FLOAT32_GAP, rel

BFLOAT16_GAP = 1e-2  # relative, kernels against the chunked form, both with bfloat16 operands
CHUNK, STATES, CHANNELS = 128, 128, 64
NAMES = ("x", "dt", "a", "b", "c")

# name: (T, rows, heads)
CASES = {
    "whole_chunks": (256, 1, 8),
    "ragged": (300, 2, 8),  # padded with steps of Δ = 0
    "three_chunks_two_head_blocks": (384, 1, 32),  # the state and dS both cross two boundaries
    "shorter_than_a_chunk": (72, 1, 8),
}


def scan_inputs(seed, t, rows, heads, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (rows, t, heads, CHANNELS)).astype(dtype)
    dt = 0.1 * jax.nn.softplus(jax.random.normal(k[1], (rows, t, heads)) - 2.0)  # decays that reach across chunks
    a = -jax.random.uniform(k[2], (heads,), minval=1.0, maxval=16.0)
    b, c = (jax.random.normal(k[i], (rows, t, STATES)).astype(dtype) for i in (3, 4))
    return (x, dt, a, b, c), jax.random.normal(k[5], (rows, t, heads, CHANNELS))  # and a fixed cotangent


def with_gradients(fn, weights):
    return jax.jit(lambda *a: (fn(*a), jax.grad(lambda *b: jnp.sum(weights * fn(*b)), argnums=range(5))(*a)))


def kernel(*args, dtype=None):
    return ssd_scan(*args, chunk=CHUNK, dtype=dtype, interpret=True)


@pytest.fixture(scope="module")
def float32_results():
    """Each case once: the kernels', the chunked form's and the recurrence's
    ``(y, gradients)``."""
    out = {}
    for seed, (name, (t, rows, heads)) in enumerate(CASES.items()):
        args, weights = scan_inputs(seed, t, rows, heads)
        out[name] = {"kernel": with_gradients(kernel, weights)(*args),
                     "chunked": with_gradients(lambda *a: ssd_chunked(*a, chunk=CHUNK), weights)(*args),
                     "sequential": with_gradients(ref.ssd_sequential, weights)(*args)}
    return out


@pytest.mark.parametrize("against", ["sequential", "chunked"])
@pytest.mark.parametrize("case", CASES)
def test_the_kernels_output_matches_in_float32(float32_results, case, against):
    y, want = float32_results[case]["kernel"][0], float32_results[case][against][0]
    assert y.shape == want.shape and y.dtype == jnp.float32
    assert rel(y, want) <= FLOAT32_GAP


@pytest.mark.parametrize("against", ["sequential", "chunked"])
@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", CASES)
def test_the_written_backward_matches_in_float32(float32_results, case, name, against):
    i = NAMES.index(name)
    got, want = float32_results[case]["kernel"][1][i], float32_results[case][against][1][i]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got, want) <= FLOAT32_GAP, rel(got, want)


@pytest.mark.parametrize("case", ["ragged", "three_chunks_two_head_blocks"])
def test_bfloat16_operands_round_where_the_chunked_form_rounds(case):
    t, rows, heads = CASES[case]
    args, weights = scan_inputs(11, t, rows, heads, dtype=jnp.bfloat16)
    y, grads = with_gradients(lambda *a: kernel(*a, dtype=jnp.bfloat16), weights)(*args)
    want, want_grads = with_gradients(lambda *a: ssd_chunked(*a, chunk=CHUNK, dtype=jnp.bfloat16), weights)(*args)
    assert y.dtype == jnp.float32 and rel(y, want) <= BFLOAT16_GAP, rel(y, want)
    for name, g, w in zip(NAMES, grads, want_grads, strict=True):
        assert g.dtype == w.dtype and rel(g, w) <= BFLOAT16_GAP, (name, rel(g, w))
    exact = with_gradients(ref.ssd_sequential, weights)(*(v.astype(jnp.float32) for v in args))
    assert rel(y, exact[0]) > 100 * FLOAT32_GAP  # and the float32 tolerance would catch the next precision down


def test_the_kernel_forgets_nothing_across_chunks_and_sees_no_future():
    (x, dt, a, b, c), _ = scan_inputs(3, 384, 1, 8)
    dt = dt * 0.5  # slow decay: step 5 still reaches step 383, two chunks on
    y0 = kernel(x, dt, a, b, c)
    y1 = kernel(x.at[:, 5].add(1.0), dt, a, b, c)
    moved = np.abs(np.asarray(y1 - y0)).max(axis=(0, 2, 3))
    assert (moved[:5] == 0).all() and (moved[5:] > 0).all()


@pytest.mark.parametrize("chunk,heads,channels,states,t,why", [
    (8, 8, 16, 16, 16, "chunk 8 is no multiple of 128"),
    (256, 64, 64, 16, 16, "d_state 16 is no multiple of 128"),
    (128, 8, 24, 128, 16, "d_head 24 is no multiple of 16"),
    # the backward keeps the state entering each chunk in VMEM: 128 chunks of 8 heads are the 32 MiB, 129 are not
    (128, 8, 64, 128, 128 * 128 + 1, "the states entering 129 chunks of 8 heads do not fit"),
])
def test_a_shape_the_kernels_refuse_falls_to_the_chunked_form_with_the_reason(chunk, heads, channels, states, t, why):
    assert why in ssd_tiles(chunk, heads, channels, states, t)
    k = jax.random.split(jax.random.key(0), 4)
    args = (jax.random.normal(k[0], (1, t, heads, channels)), jax.nn.softplus(jax.random.normal(k[1], (1, t, heads))),
            -jnp.ones((heads,)), jax.random.normal(k[2], (1, t, states)), jax.random.normal(k[3], (1, t, states)))
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd_scan(*args, chunk=chunk)
    dispatch.reset()
    try:
        y = dispatch.ssd_fn("hybrid_lm", True)(*args, chunk=chunk)  # forced, and still refused
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ssd_chunked(*args, chunk=chunk)))
        (rec,) = dispatch.records()
        assert (rec["model"], rec["op"], rec["path"]) == ("hybrid_lm", "ssd", "chunked") and why in rec["reason"]
    finally:
        dispatch.reset()


@pytest.mark.parametrize("knob,path,reason", [
    (None, "chunked", "auto: backend=cpu"),
    (False, "chunked", "pallas=False"),
    (True, "pallas", "pallas=True (forced), backend=cpu: chunk 128, 8 heads of 64, d_state 128"),
])
def test_the_knob_picks_the_path_and_the_record_says_why(knob, path, reason):
    assert ssd_tiles(CHUNK, 8, CHANNELS, STATES, 128) is None and ssd_tiles(256, 64, 64, 128, 4096) is None  # the published widths
    args, _ = scan_inputs(0, 128, 1, 8)
    dispatch.reset()
    try:
        y = dispatch.ssd_fn("hybrid_lm", knob)(*args, chunk=CHUNK)
        assert rel(y, ssd_chunked(*args, chunk=CHUNK)) <= FLOAT32_GAP
        (rec,) = dispatch.records()
        assert (rec["model"], rec["op"], rec["path"]) == ("hybrid_lm", "ssd", path) and reason in rec["reason"]
    finally:
        dispatch.reset()


def test_the_model_hands_its_knob_to_the_scan():
    """``HybridLM(pallas=True)`` at widths the kernels take: the loss and every
    leaf's gradient are the chunked model's, through ``nn.remat``."""
    cfg = HybridConfig(vocab_size=61, hidden_size=64, layer_types=("mamba", "attention", "mamba"), num_attention_heads=4,
                       num_key_value_heads=2, shared_intermediate_size=96, mamba_n_heads=2, mamba_d_head=64,
                       mamba_d_state=128, mamba_chunk_size=128, embedding_multiplier=12.0, residual_multiplier=0.22,
                       attention_multiplier=1 / 16, logits_scaling=8.0)
    tokens = jax.random.randint(jax.random.key(1), (2, 200), 0, cfg.vocab_size)
    variables = HybridLM(cfg).init(jax.random.key(0), tokens)

    def loss(variables, pallas):
        logits = HybridLM(cfg, pallas=pallas).apply(variables, tokens)
        return -jnp.mean(jax.nn.log_softmax(logits)[..., 0])

    dispatch.reset()
    try:
        got, grads = jax.jit(jax.value_and_grad(lambda v: loss(v, True)))(variables)
        paths = {(r["op"], r["path"]) for r in dispatch.records()}
        # T 200: no whole lane tiles for the conv; flash's o and lse are kept across the recomputation
        assert paths == {("attention", "flash"), ("ssd", "pallas"), ("conv", "jnp"), ("remat", "keep_attention_residuals")}
        want, want_grads = jax.jit(jax.value_and_grad(lambda v: loss(v, False)))(variables)
    finally:
        dispatch.reset()
    assert abs(float(got) - float(want)) <= FLOAT32_GAP * abs(float(want))
    gaps = jax.tree.map(rel, grads, want_grads)
    assert max(jax.tree.leaves(gaps)) <= FLOAT32_GAP, gaps


@pytest.fixture(scope="module")
def unsharded():
    args, weights = scan_inputs(0, 256, 2, 8)
    return args, weights, with_gradients(lambda *a: ssd_chunked(*a, chunk=CHUNK), weights)(*args)


@pytest.mark.parametrize("axes", [{"data": 2}, {"data": 2, "tensor": 2}, {"fsdp": 2, "tensor": 2}], ids="x".join)
def test_under_a_mesh_the_kernels_run_a_chip_s_rows_and_heads(devices, unsharded, axes):
    """A Mosaic call has no partitioning rule (jax refuses a bare one inside a
    jit over a multi-device mesh), so under an ambient mesh ``ssd_scan`` wraps
    its kernels in ``shard_map``: rows over ``data`` x ``fsdp``, heads over
    ``tensor``. ``y`` and the five gradients are the unsharded chunked form's:
    ``dA`` summed over the rows' chips, ``dB`` / ``dC`` over the heads'."""
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

    args, weights, (want, want_grads) = unsharded
    mesh = mesh_lib.create_mesh(axes, devices=devices[:int(np.prod(list(axes.values())))])
    dispatch.reset()
    try:
        with jax.sharding.set_mesh(mesh):
            fn = with_gradients(lambda *a: dispatch.ssd_fn("hybrid_lm", True)(*a, chunk=CHUNK), weights)
            local = "tensor<1x256x%dx64xf32>" % (8 // axes.get("tensor", 1))  # a chip's share of x inside the manual region
            assert local in fn.lower(*args).as_text()
            y, grads = fn(*args)
        (rec,) = dispatch.records()
        assert rec["path"] == "pallas"
    finally:
        dispatch.reset()
    assert rel(y, want) <= FLOAT32_GAP
    for name, g, w in zip(NAMES, grads, want_grads, strict=True):
        assert g.shape == w.shape and rel(g, w) <= FLOAT32_GAP, (name, rel(g, w))


# -- compiled for the chip, without the chip ----------------------------------


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e 2x2 to compile for (the TPU's compiler
    is installed here; nothing runs). Made inside the fixture, never at import."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"NOT CHECKED: Mosaic's acceptance of ssd_fwd / ssd_bwd (no v5e:2x2 topology can be described here: {e})")


def compiled_text(fn, *args):
    """``fn`` compiled for the described chips its arguments' shardings name.
    Such an executable is written to the persistent compile cache but cannot be
    read back without a chip, so the cache is off while it compiles (a worker
    runs its tests one at a time; the setting is put back)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def forward_and_backward(*a, chunk=256):
    return jax.value_and_grad(lambda *b: jnp.sum(ssd_scan(*b, chunk=chunk, dtype=jnp.bfloat16, interpret=False)),
                              argnums=range(5))(*a)


def published_widths(rows, t, rows_sharding, whole):
    heads, states = 64, 128
    return tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype, sharding in (
        ((rows, t, heads, CHANNELS), jnp.bfloat16, rows_sharding), ((rows, t, heads), jnp.float32, rows_sharding),
        ((heads,), jnp.float32, whole), ((rows, t, states), jnp.bfloat16, rows_sharding),
        ((rows, t, states), jnp.bfloat16, rows_sharding)))


@pytest.mark.parametrize("rows,t", [
    (2, 4096),  # the cell's
    (1, 16384),  # the longest T taken at these widths: 64 chunks' entering states are the backward's 32 MiB of VMEM
])
def test_both_kernels_compile_for_the_v5e_at_the_published_widths(v5e, rows, t):
    """``granite-4.0-h-micro``'s scan: Mosaic takes both bodies under the
    VMEM limit reckoned for them, the program holds exactly the two calls, and
    no ``[B, T/Q, H, Q, Q]`` array is left in it."""
    from jax.sharding import SingleDeviceSharding

    assert ssd_tiles(256, 64, CHANNELS, 128, t) is None and (t < 16384 or ssd_tiles(256, 64, CHANNELS, 128, t + 1))
    one_chip = SingleDeviceSharding(v5e[0])
    text = compiled_text(forward_and_backward, *published_widths(rows, t, one_chip, one_chip))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert f"[{rows},{t // 256},64,256,256]" not in text


def test_data_parallel_over_four_chips_each_compiles_its_own_rows(v5e):
    """The cell's scan with a batch of 8 over ``data=4``: the partitioner takes
    the program (it refuses a bare Mosaic call), and a chip's two calls see
    two rows each."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(v5e), ("data",))
    with jax.sharding.set_mesh(mesh):
        text = compiled_text(forward_and_backward,
                             *published_widths(8, 4096, NamedSharding(mesh, P("data")), NamedSharding(mesh, P())))
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and all("[2,4096,4096]" in line for line in calls), calls


# -- B and C in groups (nemotron_h: 8 groups of 8 heads; chunk 128) -------------


def grouped_inputs(seed, t, rows, heads, groups, channels=16):
    (x, dt, a, _, _), _ = scan_inputs(seed, t, rows, heads)
    k = jax.random.split(jax.random.key(seed + 50), 3)
    x = x[..., :channels]
    b, c = (jax.random.normal(k[i], (rows, t, groups, STATES)) for i in (0, 1))
    return (x, dt, a, b, c), jax.random.normal(k[2], x.shape)


def sequential(*args):
    from benchmarks.reference import nemotron_h

    return nemotron_h.ssd_sequential(*args)


@pytest.fixture(scope="module")
def grouped_results():
    """``(y, gradients)`` of the kernels, the chunked form and the recurrence at
    2 groups of 8 heads and 8 groups of 8, T = 300 (padded to three chunks of 128)."""
    out = {}
    for groups in (2, 8):
        args, weights = grouped_inputs(groups, 300, 1, 8 * groups, groups)
        out[groups] = {"kernel": with_gradients(kernel, weights)(*args),
                       "chunked": with_gradients(lambda *a: ssd_chunked(*a, chunk=CHUNK), weights)(*args),
                       "sequential": with_gradients(sequential, weights)(*args)}
    return out


@pytest.mark.parametrize("form", ["kernel", "chunked"])
@pytest.mark.parametrize("name", ("y",) + NAMES)
@pytest.mark.parametrize("groups", [2, 8])
def test_grouped_b_and_c_match_the_sequential_recurrence(grouped_results, groups, name, form):
    """A head reads its own group's ``B`` and ``C``, in ``ssd_chunked`` and in
    ``ssd_fwd`` / ``ssd_bwd`` (a head block's block specs pick the group's
    columns): ``y`` and all five gradients against the recurrence a step at a
    time (worst read 3.0e-6, a group's ``dB`` among them: summed over its heads
    alone)."""
    got, want = grouped_results[groups][form], grouped_results[groups]["sequential"]
    i = ("y",) + NAMES
    got, want = ((r[0] if name == "y" else r[1][i.index(name) - 1]) for r in (got, want))
    assert got.shape == want.shape and rel(got, want) <= FLOAT32_GAP, rel(got, want)


def test_the_groups_are_not_interchangeable():
    (x, dt, a, b, c), _ = grouped_inputs(3, 128, 1, 16, 2)
    y = kernel(x, dt, a, b, c)
    swapped = kernel(x, dt, a, b[:, :, ::-1], c[:, :, ::-1])
    assert rel(y, swapped) > 0.1
    moved = kernel(x, dt, a, b.at[:, :, 1].add(1.0), c)  # group 1's B reaches heads 8-15 alone
    changed = np.abs(np.asarray(moved - y)).max(axis=(0, 1, 3))
    assert (changed[:8] == 0).all() and (changed[8:] > 0).all()


def pr35_ssd_chunked(x, dt, a, b, c, *, chunk, dtype=None):
    """``ops/ssd.py:ssd_chunked`` as PR 35 left it (``B``, ``C`` ``[B, T, N]`` for every head), kept to hold today's to its bits."""
    dtype = dtype or x.dtype
    rows, t, h, p = x.shape
    n = b.shape[-1]
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    da = (dt * a.astype(f32)).reshape(rows, nc, chunk, h).transpose(0, 1, 3, 2)
    cs = jnp.cumsum(da, axis=-1)
    xd = (x.astype(f32) * dt[..., None]).reshape(rows, nc, chunk, h, p).transpose(0, 1, 3, 2, 4)
    b = b.reshape(rows, nc, chunk, n).astype(dtype)
    c = c.reshape(rows, nc, chunk, n).astype(dtype)
    seg = cs[..., :, None] - cs[..., None, :]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), seg, -jnp.inf))
    cb = jnp.einsum("bctn,bcsn->bcts", c, b, preferred_element_type=f32)
    y = jnp.einsum("bchts,bchsp->bchtp", (decay * cb[:, :, None]).astype(dtype), xd.astype(dtype), preferred_element_type=f32)
    to_end = jnp.exp(cs[..., -1:] - cs)
    own = jnp.einsum("bchsp,bcsn->bchpn", (xd * to_end[..., None]).astype(dtype), b, preferred_element_type=f32)
    whole = jnp.exp(cs[..., -1])

    def carry_on(state, per_chunk):
        return per_chunk[0][..., None, None] * state + per_chunk[1], state

    _, entering = jax.lax.scan(carry_on, jnp.zeros((rows, h, p, n), f32), (whole.transpose(1, 0, 2), own.transpose(1, 0, 2, 3, 4)))
    carried = jnp.einsum("bctn,bchpn->bchtp", c, entering.transpose(1, 0, 2, 3, 4).astype(dtype), preferred_element_type=f32)
    y = y + jnp.exp(cs)[..., None] * carried
    return y.transpose(0, 1, 3, 2, 4).reshape(rows, nc * chunk, h, p)[:, :t]


@pytest.mark.parametrize("form", ["chunked", "kernel"])
def test_one_group_is_todays_program_to_the_bit(form):
    """With ``B`` / ``C`` as ``[B, T, N]`` or as ``[B, T, 1, N]`` both forms give
    PR 35's ``ssd_chunked`` bits where the form is ``ssd_chunked`` (``y`` and all
    five gradients), and the kernels give each other's either way; the kernels'
    call for one group is the one they have always traced (block 0 of ``B`` and
    ``C`` for every head block, the same grid and head block)."""
    args, weights = scan_inputs(4, 300, 2, 8)
    one_group = args[:3] + (args[3][:, :, None], args[4][:, :, None])
    fn = kernel if form == "kernel" else (lambda *a: ssd_chunked(*a, chunk=CHUNK))
    y3, g3 = with_gradients(fn, weights)(*args)
    y4, g4 = with_gradients(fn, weights)(*one_group)
    same = lambda u, v: bool(jnp.all(u.reshape(v.shape) == v))  # noqa: E731
    assert same(y3, y4) and all(same(u, v) for u, v in zip(g3, g4, strict=True))
    if form == "chunked":
        y, g = with_gradients(lambda *a: pr35_ssd_chunked(*a, chunk=CHUNK), weights)(*args)
        assert same(y3, y) and all(same(u, v) for u, v in zip(g3, g, strict=True))
    else:
        text = jax.jit(lambda *a: kernel(*a, dtype=jnp.float32)).lower(*args).as_text()
        assert "tensor<2x384x128xf32>" in text  # B as [B, T, 1·N]: no group axis reaches the call


@pytest.mark.parametrize("heads,groups,why", [(64, 8, None), (16, 2, None), (64, 1, None), (12, 8, "12 heads are no multiple of 8 groups"),
                                               (32, 8, "4 heads a group are no multiple of 8")])
def test_which_grouped_shapes_tile(heads, groups, why):
    got = ssd_tiles(CHUNK, heads, CHANNELS, STATES, 8192, groups)
    assert (got is None) if why is None else (why in got), got


def test_the_dispatch_record_names_the_groups():
    args, _ = grouped_inputs(0, 128, 1, 16, 2)
    dispatch.reset()
    try:
        dispatch.ssd_fn("nemotron_h", True)(*args, chunk=CHUNK)
        (rec,) = dispatch.records()
        assert (rec["model"], rec["path"]) == ("nemotron_h", "pallas") and "16 heads of 16, d_state 128, 2 groups" in rec["reason"]
    finally:
        dispatch.reset()


def test_both_kernels_compile_for_the_v5e_at_nemotrons_widths(v5e):
    """``NVIDIA-Nemotron-3-Nano-30B-A3B``'s scan as its cell runs it: ``[2, 8192, 64, 64]``,
    8 groups of 128 states, chunk 128 (64 chunks: a head block of 8 keeps 16 MiB
    of entering states in the backward's VMEM). Mosaic takes both bodies."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e[0])
    rows, t, heads, groups = 2, 8192, 64, 8
    assert ssd_tiles(128, heads, CHANNELS, STATES, t, groups) is None
    shapes = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one) for shape, dtype in (
        ((rows, t, heads, CHANNELS), jnp.bfloat16), ((rows, t, heads), jnp.float32), ((heads,), jnp.float32),
        ((rows, t, groups, STATES), jnp.bfloat16), ((rows, t, groups, STATES), jnp.bfloat16)))
    text = compiled_text(lambda *a: forward_and_backward(*a, chunk=128), *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


# name: (tokens, top-k, width) of the expert layers the cells run
MOE_ROWS_CELLS = {"nemotron3nano_t8192": (16384, 6, 2688), "joyaiflash_t8192": (16384, 8, 2048)}


@pytest.mark.parametrize("cell", MOE_ROWS_CELLS)
def test_the_expert_layers_row_kernels_compile_for_the_v5e_at_the_cells_widths(v5e, cell):
    """``ops/moe_rows.py`` as the cell calls it (here because the described
    chips are this file's: one worker loads the TPU's library): 16,384 tokens,
    a buffer of 98,304 rows (top-6 of 2,688) or 131,072 (top-8 of 2,048).
    Mosaic takes the four calls: a copy of a row's group of eight from a
    float32 and from a bfloat16 table in HBM (it refuses a one-row slice of
    either), a bfloat16 row read through the 32-bit view of its packed pair, and
    the scalar tables in the scalar core's memory: one of a position a pair
    whole and a tile's blocks, which at 131,072 pairs is where the whole
    ``src`` beside the weights did not fit."""
    from jax.sharding import SingleDeviceSharding

    from distributed_training_pytorch_tpu.ops import moe_rows

    one = SingleDeviceSharding(v5e[0])
    n, k, d = MOE_ROWS_CELLS[cell]
    assert moe_rows.rows_refused(n, k, d, jnp.bfloat16) is None and moe_rows.rows_tile(n) == 256

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def movements(x, d_out, rows, src, n_live, weights, starts, pair, dest):
        into = moe_rows.rows_from_table(x, src, n_live, k=k, interpret=False)
        d_rows, d_weights = moe_rows.rows_from_table(d_out, src, n_live, weights, rows, k=k, out_dtype=jnp.bfloat16, interpret=False)
        out = moe_rows.tokens_from_rows(rows, starts, pair, dest, weights, tile=256, interpret=False)
        dx = moe_rows.tokens_from_rows(rows, starts, pair, dest, out_dtype=jnp.bfloat16, tile=256, interpret=False)
        return into, d_rows, d_weights, out, dx

    text = compiled_text(movements, s((n, d), jnp.bfloat16), s((n, d), jnp.float32), s((n * k, d), jnp.bfloat16), s((n * k,), jnp.int32),
                         s((), jnp.int32), s((n, k), jnp.float32), s((n // 256 + 1,), jnp.int32), s((n * k,), jnp.int32), s((n, k), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 4


# -- the causal convolution's kernels (ops/causal_conv.py), compiled for the chip -----------------------------
# Here because the described chips are this file's (one worker loads the TPU's library); the convolution's
# interpreted tests are tests/test_conv_kernel.py.

# name: (rows, T, d_inner, groups, chunk): the cells' batch and T at the configurations' mixer widths
CONV_CELLS = {"granite4h_t4096": (2, 4096, 4096, 1, 256), "nemotron3nano_t8192": (2, 8192, 4096, 8, 128)}


@pytest.mark.parametrize("cell", CONV_CELLS)
def test_the_conv_kernels_compile_for_the_v5e_at_the_cells_widths(v5e, cell):
    """Mosaic takes ``conv_fwd`` and ``conv_bwd`` under the VMEM limit reckoned
    for them, the convolution's channels read out of the whole projection."""
    from jax.sharding import SingleDeviceSharding

    from distributed_training_pytorch_tpu.ops.causal_conv import causal_conv_silu, conv_tiles

    one = SingleDeviceSharding(v5e[0])
    rows, t, d_inner, groups, _ = CONV_CELLS[cell]
    c = d_inner + 2 * groups * STATES
    assert conv_tiles(c, t, 4, d_inner) is None
    shapes = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one) for shape, dtype in (
        ((rows, t, d_inner + c + 64), jnp.bfloat16), ((4, c), jnp.float32), ((c,), jnp.float32)))
    conv = lambda *a: jnp.sum(causal_conv_silu(*a, start=d_inner, dtype=jnp.bfloat16, interpret=False).astype(jnp.float32))  # noqa: E731
    text = compiled_text(jax.value_and_grad(conv, argnums=range(3)), *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("cell", CONV_CELLS)
def test_the_mixer_hands_its_activations_between_kernels_without_copies(v5e, cell, monkeypatch):
    """The Mamba-2 mixer's forward and backward at the cell's widths with the
    scan's and the convolution's kernels compiled (on the chip ``pallas=None``
    picks both; here the test steers them off the interpreter): four Mosaic
    calls, the convolution reads ``xBC`` out of ``in_proj``'s output where it
    lies (``[B, F, T]``: no slice of it is made), no ``[B, T, C]`` / ``[B, C, T]``
    array of the convolution's or the projection's width is copied or
    transposed between them, and the backward kernel carries ``mamba_conv`` in
    its scope (a ``custom_vjp``'s backward half does not inherit its call
    site's)."""
    import re

    from jax.sharding import SingleDeviceSharding

    from distributed_training_pytorch_tpu.models.hybrid_lm import MambaMixer
    from distributed_training_pytorch_tpu.ops import causal_conv, ssd

    for module in (causal_conv, ssd):
        monkeypatch.setattr(module, "resolve_interpret", lambda interpret=None: False)
    one = SingleDeviceSharding(v5e[0])
    rows, t, d_inner, groups, chunk = CONV_CELLS[cell]
    cfg = HybridConfig(vocab_size=64, hidden_size=2048, layer_types=("mamba",), num_attention_heads=32, num_key_value_heads=8,
                       mamba_n_heads=64, mamba_d_head=CHANNELS, mamba_d_state=STATES, mamba_n_groups=groups,
                       mamba_chunk_size=chunk)
    mixer = MambaMixer(cfg, jnp.bfloat16, pallas=True)
    params = jax.eval_shape(lambda: mixer.init(jax.random.key(0), jnp.zeros((1, t, cfg.hidden_size), jnp.bfloat16)))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), params)
    u = jax.ShapeDtypeStruct((rows, t, cfg.hidden_size), jnp.bfloat16, sharding=one)
    text = compiled_text(jax.value_and_grad(lambda p, u: jnp.sum(mixer.apply(p, u).astype(jnp.float32)), argnums=(0, 1)),
                         params, u)
    calls = re.findall(r"%((?:conv|ssd)_(?:fwd|bwd))\.?\d* = [^\n]*? custom-call\(", text)
    assert sorted(calls) == ["conv_bwd", "conv_fwd", "ssd_bwd", "ssd_fwd"], calls
    c = d_inner + 2 * groups * STATES
    f = d_inner + c + 64
    wide = "|".join(f"{rows},{t},{n}\\]|{rows},{n},{t}\\]" for n in (c, f))
    moved = [line.strip()[:160] for line in text.splitlines() if re.search(rf"\[({wide})\S* (copy|transpose)\(", line)]
    assert not moved, moved
    (fwd,) = [line for line in text.splitlines() if re.search(r"%conv_fwd\.?\d* = ", line)]
    assert f"operand_layout_constraints={{bf16[{rows},{f},{t}]" in fwd
    (bwd,) = [line for line in text.splitlines() if re.search(r"%conv_bwd\.?\d* = ", line)]
    assert re.search(r'op_name="[^"]*mamba_conv[^"]*"', bwd), bwd[:400]


# -- latent attention's flash widths (joyaiflash_t8192: queries and keys 192, values 128) --


def test_flash_compiles_for_the_v5e_at_latent_attentions_two_widths(v5e):
    """The cell's attention, ``[2, 8192, 32, 192]`` queries and keys against
    ``[2, 8192, 32, 128]`` values, forward and backward: Mosaic takes both
    bodies (the backward under the VMEM limit reckoned with each width in its
    own lanes), the program holds the two calls, and v, dO and dv stay 128
    wide (nothing is padded to q's 192)."""
    from jax.sharding import SingleDeviceSharding

    from distributed_training_pytorch_tpu.ops.pallas import flash_attention

    one_chip = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)

    def step(q, k, v):
        out, back = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False), q, k, v)
        return back(out)

    text = compiled_text(step, qk, qk, v)
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(line.split("=")[0].strip().lstrip("%").split(".")[0] for line in calls) == ["flash_dqkv", "flash_fwd"], calls
    assert all("[2,32,8192,192]" not in line.split("custom-call(")[0] or "flash_dqkv" in line for line in calls)
    assert "bf16[2,32,8192,128]" in calls[0] + calls[1] and "[2,32,8192,256]" not in text
