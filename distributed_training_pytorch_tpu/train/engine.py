"""Jitted train/eval step engine.

TPU-native replacement for the reference hot path (``trainer/trainer.py:143-156``
+ ``example_trainer.py:73-89``): where the reference does per-batch H2D copy,
DDP forward, backward with bucketed NCCL all-reduce, optimizer step, and a
``loss.item()`` device sync *per step*, this engine compiles the whole step —
loss, ``jax.grad``, cross-device gradient reduction, and the optax update —
into one XLA program over a named mesh. Gradient synchronization needs no
explicit collective: the batch is sharded over the ``data`` axis and XLA
inserts (and overlaps) the all-reduce itself. Params are replicated for pure
DP, or sharded over ``fsdp``/``tensor`` axes per ``parallel.sharding`` rules
(ZeRO-3 / Megatron-TP analogs) — the step body is identical either way; only
the sharding annotations change. Metrics stay on device; the host never
blocks per step.

Gradient accumulation (BASELINE config 5) runs as a ``lax.scan`` over
microbatches inside the same compiled step.

Mixed precision (ISSUE 3): a ``precision.Policy`` casts params and float
inputs to its compute dtype at the loss-fn boundary INSIDE the compiled step
— master weights, grads, and optimizer state stay in ``param_dtype`` (fp32)
because the grads of the uncast params flow back through the cast's
transpose. Loss scaling (``precision.loss_scale``) rides in
``state.loss_scale``: the loss is multiplied by the scale before ``grad``,
grads divided after, and a ``DynamicScale`` folds torch.amp's grow/backoff/
skip protocol into the same non-finite guard ``nan_guard`` uses, so an
overflow-skip and a nan-skip are one event counted once. The default fp32
policy is detected statically and traces the exact pre-precision program.
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.parallel import sharding as sharding_lib
from distributed_training_pytorch_tpu.precision import get_policy, is_dynamic
from distributed_training_pytorch_tpu.train.state import TrainState

# A LossFn maps (params, model_state, batch, rng, train) ->
#   (loss, (metrics dict, new_model_state)).
LossFn = Callable[[Any, Any, Any, jax.Array, bool], tuple[jax.Array, tuple[Mapping, Any]]]


class NonFiniteLossError(FloatingPointError):
    """Raised by the trainer's ``nan_policy="raise"`` when a step produced a
    non-finite loss (the functional analog of torch's anomaly detection)."""


def stack_chain_batch(batch, chain_length: int) -> Any:
    """The chain-stacked abstract window for a per-step batch: every leaf
    gains a leading ``chain_length`` axis (the ``device_prefetch_chained``
    staging layout the chained program consumes). The ONE stacking rule for
    every observability probe of the chained program — memory attribution
    (``memory.analysis``), the donation audit (``analysis.hlo_audit``), and
    the communication audit (``analysis.comm_audit``) all build the probe
    window here, so the audited window shape cannot drift from the shape
    :meth:`TrainEngine.train_steps_chained` dispatches."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((int(chain_length),) + tuple(x.shape), x.dtype),
        batch,
    )


def xla_flag_options(flags: str | None) -> dict[str, str]:
    """Parse an ``XLA_FLAGS``-style string into a ``compiler_options`` dict
    for :meth:`TrainEngine.compile_train_step` /
    :meth:`TrainEngine.compile_chained_train_steps`.

    ``"--xla_a=true --xla_b=2"`` -> ``{"xla_a": "true", "xla_b": "2"}``; a
    bare ``--xla_flag`` maps to ``"true"``. This is the bridge the autotuner
    (``train/autotune.py``) uses to sweep latency-hiding / async-collective
    flags per-compile instead of mutating the global ``XLA_FLAGS`` env, which
    only applies at backend init — a sweep that restarts the process per
    candidate would pay compile + init for every flag set and could never
    share one warm engine.
    """
    options: dict[str, str] = {}
    for tok in (flags or "").split():
        if not tok.startswith("--"):
            raise ValueError(f"XLA flag {tok!r} must start with '--'")
        key, eq, value = tok[2:].partition("=")
        if not key.startswith("xla"):
            raise ValueError(f"{tok!r} is not an --xla_* flag")
        options[key] = value if eq else "true"
    return options


def make_supervised_loss(model, criterion: Callable) -> LossFn:
    """Build the standard supervised LossFn from a Flax module + criterion.

    ``criterion(outputs, batch) -> (loss, metrics)`` is the functional analog of
    the reference's ``build_criterion`` hook (``example_trainer.py:55-58``);
    the returned metrics dict mirrors the ``{"ce_loss": ...}`` contract of
    ``train_step`` (``example_trainer.py:89``).
    """

    def loss_fn(params, model_state, batch, rng, train):
        variables = {"params": params, **model_state}
        mutable = list(model_state) if train else []
        kwargs = {"mutable": mutable} if mutable else {}
        if train:
            # dropout + droppath (stochastic depth, ConvNeXt) streams; Flax
            # ignores streams a model doesn't declare.
            kwargs["rngs"] = {"dropout": rng, "droppath": jax.random.fold_in(rng, 1)}
        out = model.apply(variables, batch["image"], train=train, **kwargs)
        outputs, new_model_state = out if mutable else (out, model_state)
        loss, metrics = criterion(outputs, batch)
        return loss, (metrics, new_model_state)

    return loss_fn


class TrainEngine:
    """Owns the compiled train/eval steps and the state layout on the mesh.

    Collapses the reference's four mutable hooks (model/criterion/optimizer/
    scheduler, ``trainer/trainer.py:38-41``) into: a ``LossFn``, an optax
    ``GradientTransformation`` (optimizer + schedule fused), and a mesh.
    """

    def __init__(
        self,
        loss_fn: LossFn,
        optimizer: optax.GradientTransformation,
        mesh: Mesh,
        *,
        accum_steps: int = 1,
        schedule: optax.Schedule | None = None,
        donate_state: bool = True,
        sharding_rules: Sequence | None = None,
        fsdp_min_size: int = 2**18,
        nan_guard: bool = False,
        precision=None,
        loss_scale=None,
        stats: bool = False,
    ):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.accum_steps = int(accum_steps)
        self.schedule = schedule
        # Mixed precision: the policy is static (trace-time) config; the
        # loss-scale STATE lives in TrainState (init_state seeds it with this
        # initial value) so it survives checkpoint/resume and chained scans.
        self.precision = get_policy(precision)
        self.initial_loss_scale = loss_scale
        # Non-finite step guard (graceful-degradation support): when on, a
        # step whose loss or grads contain NaN/Inf leaves params/opt_state/
        # model_state UNTOUCHED (step and rng still advance, so the data and
        # dropout streams move past the poison batch) and reports
        # metrics["nonfinite"]=1. All inside the compiled step — no host
        # sync. Off by default: the where-select touches every state leaf.
        self.nan_guard = bool(nan_guard)
        # Telemetry train-health stats (ISSUE 4): grad/param norms, update
        # ratio, nonfinite flag computed INSIDE the step and returned as
        # ordinary metrics — they ride chained windows as scan outputs with
        # zero extra host syncs, and reading the dataflow (norm reductions
        # hang off grads/params/updates, never feed back into them) keeps
        # params bit-exact with a stats-off run. Off by default: the
        # historical program traces byte-identically.
        self.stats = bool(stats)
        self.sharding_rules = sharding_rules
        self.fsdp_min_size = fsdp_min_size
        self._batch_sharding = mesh_lib.batch_sharding(mesh)
        self._replicated = NamedSharding(mesh, P())
        self._donate = (0,) if donate_state else ()
        # Param/opt-state sharding tree — computed from the state structure on
        # first use (init_state or the first step); replicated for pure DP,
        # rule/FSDP-sharded otherwise (parallel.sharding).
        self._state_sharding = None
        self._state_structure = None
        self._train_step = None
        self._eval_step = None
        # Chained executables, one per window length (jit itself caches per
        # input shape, so a given length never retraces for the same batch
        # shapes). Tail windows shorter than the chain length are the
        # trainer's job to run single-step — compiling a fresh chain per tail
        # length would pay a full-model compile for one window.
        self._chained_fns: dict[int, Any] = {}
        # Compilation counters: bumped once per TRACE of each compiled body
        # (a jit cache hit does not re-execute the Python body). The
        # scripts/retrace_guard.py CI gate asserts these stay at 1 per shape,
        # so a dispatch-path change that silently retraces fails fast.
        self.trace_counts: Counter = Counter()
        # Memoized observability probe executables (compile_step_probe),
        # keyed by abstract shapes: the MFU probe and the profile capture's
        # roofline join both want the identical program — one compile serves
        # both.
        self._step_probe_cache: dict = {}

    def state_sharding(self, state_or_abstract) -> Any:
        """The NamedSharding tree this engine lays state out with.

        Contract: one engine serves ONE state — the tree is computed from the
        first state seen (init_state or the first step), cached, and later
        calls must present the same tree structure AND leaf shapes/dtypes (a
        second model/state on a reused engine would otherwise silently get the
        first one's shardings: at best a cryptic XLA error, at worst wrong
        layouts)."""
        # str(dtype) rather than result_type: typed PRNG-key leaves carry an
        # extended dtype that result_type rejects.
        leaf_shapes = jax.tree.map(
            lambda x: (tuple(x.shape), str(getattr(x, "dtype", None))), state_or_abstract
        )
        structure = (jax.tree.structure(state_or_abstract), tuple(jax.tree.leaves(leaf_shapes)))
        if self._state_sharding is None:
            self._state_structure = structure
            if self.sharding_rules is None and not any(
                self.mesh.shape.get(a, 1) > 1 for a in (mesh_lib.FSDP_AXIS, mesh_lib.TENSOR_AXIS)
            ):
                self._state_sharding = self._replicated
            else:
                self._state_sharding = sharding_lib.state_shardings(
                    state_or_abstract,
                    self.mesh,
                    self.sharding_rules or (),
                    fsdp_min_size=self.fsdp_min_size,
                )
        elif structure != self._state_structure:
            raise ValueError(
                "this TrainEngine is already bound to a state with a "
                "different structure or leaf shapes/dtypes (one engine serves "
                "one model/state); build a new engine for the new state."
            )
        return self._state_sharding

    def state_sharding_tree(self, state_or_abstract) -> Any:
        """:meth:`state_sharding` expanded to one ``NamedSharding`` per leaf
        (pure DP returns a SINGLE replicated sharding there — consumers that
        need per-leaf shard shapes, like the memory subsystem's per-device
        byte accounting and the checkpoint sharding record, want the
        broadcast tree)."""
        return sharding_lib.expand_shardings(
            state_or_abstract, self.state_sharding(state_or_abstract)
        )

    def _build_steps(self, state) -> None:
        if self._train_step is not None:
            return
        state_sharding = self.state_sharding(state)

        def train_step(state, batch):
            self.trace_counts["train_step"] += 1
            return self._train_step_impl(state, batch)

        def eval_step(state, batch):
            self.trace_counts["eval_step"] += 1
            return self._eval_step_impl(state, batch)

        self._train_step = jax.jit(
            train_step,
            in_shardings=(state_sharding, self._batch_sharding),
            out_shardings=(state_sharding, self._replicated),
            donate_argnums=self._donate,
        )
        self._eval_step = jax.jit(  # jaxlint: disable=missing-donate-on-jit -- eval only READS state: donating would consume the very buffers the next train step needs
            eval_step,
            in_shardings=(state_sharding, self._batch_sharding),
            out_shardings=self._replicated,
        )

    # -- state ------------------------------------------------------------

    def init_state(self, rng: jax.Array, init_fn: Callable[[jax.Array], dict]) -> TrainState:
        """Initialize state directly into this engine's sharded layout.

        ``init_fn(rng) -> variables`` (a Flax ``model.init`` closure). The
        analog of ``build_model`` + ``model.to(local_rank)`` + the DDP ctor's
        initial parameter broadcast (``trainer/trainer.py:38,51-52``) — init
        is jitted with the engine's state sharding as output sharding:
        replicated for pure DP (every device holds identical params, no
        explicit broadcast), or fsdp/tensor-sharded per the engine's rules —
        in which case NO device ever holds the full parameter set.
        """
        init_rng, state_rng = jax.random.split(rng)

        def make(init_rng, state_rng):
            variables = init_fn(init_rng)
            params = variables.pop("params")
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self.optimizer.init(params),
                model_state=dict(variables),
                rng=state_rng,
                loss_scale=self.initial_loss_scale,
            )

        # Shape-infer the state, derive its sharding tree, then materialize
        # directly into that layout — params larger than one device's HBM
        # never exist unsharded anywhere.
        abstract = jax.eval_shape(make, init_rng, state_rng)
        out_shardings = self.state_sharding(abstract)
        with self._ambient_mesh():  # in-model constraints resolve (see below)
            return jax.jit(make, out_shardings=out_shardings)(init_rng, state_rng)

    # -- compiled bodies --------------------------------------------------

    def _wrap_loss(self, scale_state):
        """The loss-fn boundary where mixed precision happens: cast params +
        float inputs to the policy's compute dtype, cast the loss back to
        fp32, and multiply by the loss scale so ``grad`` differentiates the
        SCALED loss. Aux carries the raw (unscaled, fp32) loss for metrics.

        With the fp32 policy and no dynamic scale this is a pure aux
        restructure — zero ops added, the compiled program is bit-identical
        to the pre-precision engine (test-enforced)."""
        policy = self.precision
        base = self.loss_fn
        dynamic = is_dynamic(scale_state)
        if not policy.active and not dynamic:
            def wrapped(params, model_state, batch, rng, train):
                loss, (metrics, new_ms) = base(params, model_state, batch, rng, train)
                return loss, (loss, metrics, new_ms)

            return wrapped

        def wrapped(params, model_state, batch, rng, train):
            loss, (metrics, new_ms) = base(
                policy.cast_params(params),
                model_state,
                policy.cast_inputs(batch),
                rng,
                train,
            )
            loss = policy.cast_output(loss)
            grad_loss = scale_state.scale_loss(loss) if dynamic else loss
            return grad_loss, (loss, metrics, new_ms)

        return wrapped

    def _grads_and_metrics(self, state: TrainState, batch, rng):
        scale_state = state.loss_scale
        dynamic = is_dynamic(scale_state)
        grad_fn = jax.value_and_grad(self._wrap_loss(scale_state), has_aux=True)
        if self.accum_steps <= 1:
            (_, (loss, metrics, new_ms)), grads = grad_fn(
                state.params, state.model_state, batch, rng, True
            )
            if dynamic:
                grads = scale_state.unscale_grads(grads)
            return grads, loss, metrics, new_ms

        # Microbatch scan: reshape [B, ...] -> [A, B/A, ...] and accumulate.
        def to_micro(x):
            return x.reshape((self.accum_steps, x.shape[0] // self.accum_steps) + x.shape[1:])

        micro = jax.tree.map(to_micro, batch)

        def body(carry, xs):
            mb, micro_idx = xs
            grads_acc, loss_acc, metrics_acc, ms = carry
            mb_rng = jax.random.fold_in(rng, micro_idx)
            (_, (loss, metrics, ms)), grads = grad_fn(state.params, ms, mb, mb_rng, True)
            grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
            loss_acc = loss_acc + loss
            metrics_acc = jax.tree.map(jnp.add, metrics_acc, dict(metrics))
            return (grads_acc, loss_acc, metrics_acc, ms), None

        zero_grads = jax.tree.map(jnp.zeros_like, state.params)
        # Trace one microbatch to learn the metrics structure for the carry.
        _, (metrics0, _) = jax.eval_shape(
            lambda p, ms, b: self.loss_fn(p, ms, b, rng, True),
            state.params,
            state.model_state,
            jax.tree.map(lambda x: x[0], micro),
        )
        zero_metrics = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), dict(metrics0))
        (grads, loss, metrics, new_ms), _ = jax.lax.scan(
            body,
            (zero_grads, jnp.zeros(()), zero_metrics, state.model_state),
            (micro, jnp.arange(self.accum_steps)),
        )
        if dynamic:
            grads = scale_state.unscale_grads(grads)  # accumulated scaled
        inv = 1.0 / self.accum_steps
        grads = jax.tree.map(lambda g: g * inv, grads)
        metrics = jax.tree.map(lambda m: m * inv, metrics)
        return grads, loss * inv, metrics, new_ms

    def _train_step_impl(self, state: TrainState, batch):
        step_rng = jax.random.fold_in(state.rng, state.step)
        grads, loss, metrics, new_ms = self._grads_and_metrics(state, batch, step_rng)
        # HLO metadata only: splits a device trace of the step into forward +
        # backward, the loss head (ops/losses.py) and the optimizer.
        with jax.named_scope("optimizer"):
            updates, new_opt_state = self.optimizer.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = dict(metrics)
        if self.stats:
            from distributed_training_pytorch_tpu.telemetry.stats import (
                train_health_stats,
            )

            # setdefault: a user criterion that already reports one of these
            # keys wins; the guard below overwrites `nonfinite` with its
            # exact per-leaf predicate when armed (the stats flag derives
            # from the reduced grad norm — same answer on real poison, but
            # the guard's version is the skip-accounting source of truth).
            for key, value in train_health_stats(
                loss=loss, grads=grads, params=state.params, updates=updates
            ).items():
                metrics.setdefault(key, value)
        scale_state = state.loss_scale
        dynamic = is_dynamic(scale_state)
        if self.nan_guard or dynamic:
            # ONE unified guard: a dynamic-scale overflow and a nan_policy
            # poison are the same predicate, the same conditional apply, and
            # the same metrics["nonfinite"] flag — a step is counted skipped
            # once, never twice.
            ok = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                ok &= jnp.all(jnp.isfinite(g))
            keep = lambda new, old: jax.tree.map(  # noqa: E731
                lambda n, o: jnp.where(ok, n, o), new, old
            )
            new_params = keep(new_params, state.params)
            new_opt_state = keep(new_opt_state, state.opt_state)
            new_ms = keep(new_ms, state.model_state)
            metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
            if dynamic:
                # Grow/backoff runs inside the step; the scale THIS step used
                # is the observable metric (the post-adjust value is next
                # step's metric).
                scale_state = scale_state.adjust(ok)
                metrics["loss_scale"] = state.loss_scale.scale
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            model_state=new_ms,
            loss_scale=scale_state,
        )
        metrics.setdefault("loss", loss)
        if self.schedule is not None:
            metrics["lr"] = self.schedule(state.step)
        return new_state, metrics

    def _eval_step_impl(self, state: TrainState, batch):
        # Eval is deterministic (no dropout); the rng is passed only to keep
        # the LossFn signature uniform. The precision policy's boundary casts
        # apply to eval too (scale never does: no grads to protect).
        _, (_, metrics, _) = self._wrap_loss(None)(
            state.params, state.model_state, batch, state.rng, False
        )
        return dict(metrics)

    # -- public API -------------------------------------------------------

    def _ambient_mesh(self):
        """Make ``self.mesh`` the ambient mesh while tracing/dispatching.

        Models annotate internal layouts with bare ``PartitionSpec``s via
        ``with_sharding_constraint`` (e.g. ``parallel.moe``'s expert-sharded
        buffers) — those resolve against the ambient mesh, which plain
        ``jax.jit`` with explicit NamedShardings does NOT establish. Without
        this, in-model constraints would silently no-op on the engine path."""
        return jax.sharding.set_mesh(self.mesh)

    def train_step(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        """One compiled optimizer step on a global batch. Metrics are device
        arrays (global means) — call ``jax.device_get`` only when logging."""
        self._build_steps(state)
        with self._ambient_mesh():
            return self._train_step(state, batch)

    def eval_step(self, state: TrainState, batch) -> dict:
        """Collective validation step — replaces the reference's rank-0-only,
        non-distributed ``validate`` (``trainer/trainer.py:184-206``): every
        device evaluates its shard and metrics reduce globally."""
        self._build_steps(state)
        with self._ambient_mesh():
            return self._eval_step(state, batch)

    def shard_batch(self, batch):
        """Host-local rows -> one global data-sharded array (see
        ``parallel.mesh.global_array_from_host_local``)."""
        return mesh_lib.global_array_from_host_local(batch, self.mesh)

    def train_steps_chained(self, state: TrainState, stacked_batch, length: int):
        """Run ``length`` train steps as ONE compiled on-device program.

        ``stacked_batch`` leaves carry a leading step axis of size ``length``
        (``parallel.mesh.chain_batch_sharding`` layout — the
        ``data.device_prefetch_chained`` staging format): a ``lax.scan``
        carries the state and slices one per-step batch per trip, so a single
        dispatch executes the whole window back-to-back on device. Per-step
        RNG still advances via ``state.step``, and the nan-guard and
        microbatch-accumulation paths run inside the scan body unchanged —
        chained execution is bit-identical to ``length`` sequential
        :meth:`train_step` calls on the same data (test-enforced).

        Returns ``(state, metrics)`` where every metric leaf has leading axis
        ``length`` — per-step values as scan outputs, so callers keep exact
        per-step accounting (loss logging, ``nonfinite`` counts) without any
        extra host sync.

        Executables are cached per ``length`` (and per shape, by jit): call
        with ONE window length and route shorter tails to :meth:`train_step`
        instead of paying a fresh full-model compile per tail length.
        """
        if length < 1:
            raise ValueError(f"length must be >= 1, got {length}")
        self._build_steps(state)
        fn = self._chained_step_fn(length, state)
        with self._ambient_mesh():
            return fn(state, stacked_batch)

    def _chained_step_fn(self, length: int, state_or_abstract):
        """The jitted chained-window program for ``length`` (built and cached
        on first use). Split out of :meth:`train_steps_chained` so the REAL
        dispatch program can be *lowered* on abstract avals without executing
        a window — which is how ``tests/test_analysis.py`` pins the static
        audit's chained probe (:meth:`lower_step_probe`; no trace-count side
        effects) byte-equal to this program: the audit verifies what the
        trainer actually runs, enforced rather than claimed."""
        fn = self._chained_fns.get(length)
        if fn is None:
            state_sharding = self.state_sharding(state_or_abstract)
            chain_sharding = mesh_lib.chain_batch_sharding(self.mesh)

            def chained(st, sbatch):
                self.trace_counts[f"chained_{length}"] += 1
                # _train_step_impl(state, batch) -> (state, metrics) is
                # exactly scan's (carry, x) -> (carry, y) contract; ys stack
                # into the per-step metrics. unroll=length: a rolled While
                # body reads its per-step batch through a dynamic-slice whose
                # layout can differ from the standalone step's input, and the
                # conv wgrad reduction order shifts with it. An unrolled
                # window holds the single step's arithmetic, not its bits: on
                # the CPU backend the two still differ by a few ULPs of a
                # leaf's largest value (tests/test_engine.py:
                # CHAINED_VS_SINGLE_ULPS; not checked on the TPU). Cost:
                # compile time linear in `length`, the right trade at the
                # 4-32 window sizes chaining targets.
                return jax.lax.scan(
                    self._train_step_impl, st, sbatch, unroll=length
                )

            fn = jax.jit(
                chained,
                in_shardings=(state_sharding, chain_sharding),
                out_shardings=(state_sharding, self._replicated),
                donate_argnums=self._donate,
            )
            self._chained_fns[length] = fn
        return fn

    def unstack_window(self, stacked_batch, index: int):
        """Slice step ``index``'s batch out of a chain-stacked window, laid
        out as the single-step batch sharding — the trainer's fallback when a
        staged window must run step-by-step after all (fault injection
        active in its range)."""
        return jax.tree.map(
            lambda x: jax.device_put(x[index], self._batch_sharding), stacked_batch
        )

    def compile_train_step(self, state: TrainState, batch, *, compiler_options=None):
        """AOT-compile the train step for these shapes and return the compiled
        executable (callable as ``compiled(state, batch)``). Supported surface
        for benchmarking: ``compiled.cost_analysis()`` exposes XLA's FLOP
        estimate for MFU math.

        ``compiler_options`` passes per-compile XLA flags (e.g.
        ``{"xla_tpu_scoped_vmem_limit_kib": "49152"}`` — measured ~9% faster
        on the VGG16/v5e step; see utils/tpu.py) without touching global
        XLA_FLAGS."""
        self._build_steps(state)
        with self._ambient_mesh():
            lowered = self._train_step.lower(state, batch)
        if compiler_options:
            return lowered.compile(compiler_options=dict(compiler_options))
        return lowered.compile()

    def lower_step_probe(self, state, batch, *, donate: bool = False,
                         chain_length: int | None = None):
        """Lower (but do not compile) the observability probe — the
        pre-optimization module text (``.as_text()``) is what the static
        audit's precision-leak check reads: program *semantics* (a bf16
        policy's bf16 dots), where the compiled text on CPU shows the
        backend's f32-promotion of those same dots. See
        :meth:`compile_step_probe` for the donate/chain_length contract."""
        abstract_state, abstract_batch = jax.eval_shape(
            lambda s, b: (s, b), state, batch
        )
        state_sharding = self.state_sharding(state)
        if chain_length is None:
            fn = self._train_step_impl
            batch_sharding = self._batch_sharding
        else:
            if chain_length < 1:
                raise ValueError(f"chain_length must be >= 1, got {chain_length}")
            length = int(chain_length)

            def chained(st, sbatch):
                # The real chained window program (_chained_step_fn) minus
                # its trace-counting wrapper: same name, same scan, same
                # unroll, same shardings — lowered-HLO equality with the
                # dispatch program is pinned by test_analysis.py, so the two
                # constructions cannot drift apart silently.
                return jax.lax.scan(self._train_step_impl, st, sbatch, unroll=length)

            fn = chained
            batch_sharding = mesh_lib.chain_batch_sharding(self.mesh)
        probe = jax.jit(
            fn,
            in_shardings=(state_sharding, batch_sharding),
            out_shardings=(state_sharding, self._replicated),
            # Mirror the dispatch path's donation EXACTLY: an engine built
            # with donate_state=False runs undonated programs, and the
            # donation audit must see (and fail on) that program, not a
            # donated twin that never dispatches.
            donate_argnums=self._donate if donate else (),
        )
        with self._ambient_mesh():
            return probe.lower(abstract_state, abstract_batch)

    def compile_step_probe(self, state, batch, *, donate: bool = False,
                           chain_length: int | None = None):
        """Observability-only compiled copy of the train program (no
        counting wrapper) on abstract avals: one extra off-hot-path XLA
        compile, but the dispatch executables, their jit caches, and
        ``trace_counts`` are untouched — the retrace-guard contract holds
        with telemetry/profiling on (test-enforced). ``state``/``batch`` may
        be concrete arrays or ``ShapeDtypeStruct`` trees (no data is read).

        ``donate=False, chain_length=None`` (default) is the historical
        probe: the single step, undonated — feeds :meth:`step_cost_analysis`
        (the MFU probe) and the profile capture's per-op roofline join.
        ``donate=True`` mirrors the dispatch path's ``donate_argnums`` so the
        static audit (``analysis.hlo_audit``) can verify input-output buffer
        aliasing on the program the trainer actually runs; ``chain_length=N``
        probes the chained-window program (``batch`` then carries the leading
        step axis). Memoized per (abstract shape, donate, chain_length), so a
        run with both telemetry and profiling on pays each probe compile
        once, not once per consumer."""
        abstract_state, abstract_batch = jax.eval_shape(
            lambda s, b: (s, b), state, batch
        )
        leaves, treedef = jax.tree.flatten((abstract_state, abstract_batch))
        key = (
            treedef,
            tuple((leaf.shape, str(leaf.dtype)) for leaf in leaves),
            bool(donate),
            chain_length,
        )
        cached = self._step_probe_cache.get(key)
        if cached is not None:
            return cached
        compiled = self.lower_step_probe(
            state, batch, donate=donate, chain_length=chain_length
        ).compile()
        self._step_probe_cache[key] = compiled
        return compiled

    def with_accum(self, accum_steps: int) -> "TrainEngine":
        """An observability twin of this engine at a different
        grad-accumulation factor — same loss fn, optimizer, mesh, precision,
        guard, and donation, fresh jit caches. ``memory.preflight`` probes
        these (abstract lowerings only, never dispatched) to recommend the
        microbatch factor that fits device memory; the twin shares nothing
        with this engine's executables, so probing it cannot perturb the
        dispatch path."""
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        return TrainEngine(
            self.loss_fn,
            self.optimizer,
            self.mesh,
            accum_steps=accum_steps,
            schedule=self.schedule,
            donate_state=bool(self._donate),
            sharding_rules=self.sharding_rules,
            fsdp_min_size=self.fsdp_min_size,
            nan_guard=self.nan_guard,
            precision=self.precision,
            loss_scale=self.initial_loss_scale,
            stats=self.stats,
        )

    def with_mesh(self, mesh: Mesh) -> "TrainEngine":
        """An observability twin of this engine on a DIFFERENT mesh — same
        loss fn, optimizer, precision, guard, donation, sharding rules, and
        accumulation, fresh jit caches and a fresh state-sharding layout.
        ``memory.preflight`` probes these (abstract lowerings only, never
        dispatched) to answer "would this program fit with fsdp=N" — the
        sharded-fit recommendation on predicted OOM. The ``with_accum``
        contract holds: the twin shares nothing with this engine's
        executables, so probing it cannot perturb the dispatch path."""
        return TrainEngine(
            self.loss_fn,
            self.optimizer,
            mesh,
            accum_steps=self.accum_steps,
            schedule=self.schedule,
            donate_state=bool(self._donate),
            sharding_rules=self.sharding_rules,
            fsdp_min_size=self.fsdp_min_size,
            nan_guard=self.nan_guard,
            precision=self.precision,
            loss_scale=self.initial_loss_scale,
            stats=self.stats,
        )

    def step_cost_analysis(self, state, batch) -> dict:
        """XLA's cost analysis (FLOPs, bytes accessed, ...) of ONE train step
        for these shapes — the telemetry MFU probe, via
        :meth:`compile_step_probe`. The scan conventions match
        ``utils.hlo_flops``: for a chained run this single-step figure IS the
        per-step figure."""
        compiled = self.compile_step_probe(state, batch)
        from distributed_training_pytorch_tpu.utils.hlo_flops import xla_cost_analysis

        return xla_cost_analysis(compiled)

    def compile_chained_train_steps(
        self, state: TrainState, batch, length: int, *, compiler_options=None
    ):
        """AOT-compile ``length`` train steps chained on-device over one batch
        (``lax.scan`` carrying the state; per-step RNG still advances via
        ``state.step``). One dispatch then runs ``length`` real steps
        back-to-back — for measuring sustained device step time without
        per-dispatch host latency inside the window. Returns ``compiled(state, batch) -> (state,
        last_metrics)``."""
        if length < 1:
            raise ValueError(f"length must be >= 1, got {length}")
        self._build_steps(state)
        state_sharding = self.state_sharding(state)

        def chained(state, batch):
            def body(st, _):
                st, metrics = self._train_step_impl(st, batch)
                return st, metrics

            state, metrics = jax.lax.scan(body, state, None, length=length)
            return state, jax.tree.map(lambda m: m[-1], metrics)

        jitted = jax.jit(
            chained,
            in_shardings=(state_sharding, self._batch_sharding),
            out_shardings=(state_sharding, self._replicated),
            donate_argnums=self._donate,
        )
        with self._ambient_mesh():
            lowered = jitted.lower(state, batch)
        if compiler_options:
            return lowered.compile(compiler_options=dict(compiler_options))
        return lowered.compile()
