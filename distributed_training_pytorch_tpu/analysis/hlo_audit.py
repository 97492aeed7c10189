"""Compiled-program (HLO) audit: verify invariants on the *real* programs.

The jaxlint layer (``analysis.lint``) reads source; this layer reads what
XLA actually compiled. Three invariants, each grounded in a measured cost:

**Donation** — every param/optimizer-state input buffer of the train step
must be input-output aliased (``donate_argnums`` honored end to end). An
undonated state doubles its memory for the duration of the step AND forces
a copy. The
check parses the compiled module's ``input_output_alias`` header and sizes
any undonated leaf with ``utils.hlo_flops.aval_bytes``.

**Precision leaks** — under a bf16/fp16 policy, no fp32 ``dot``/
``convolution`` may appear: the policy casts at the loss boundary, and an
f32 matmul sneaking in (a forgotten cast on a new branch) silently halves
MXU throughput for that op. Ops are bucketed by the profiling package's
shared categorizer (``profiling.categories.categorize``) so "what counts
as MXU work" has exactly one definition in the codebase. This check reads
the **lowered (pre-optimization) module**: program semantics. The compiled
text would lie on CPU — the CPU backend legitimately promotes bf16 dots to
f32 internally (measured: ``convert -> f32 dot -> convert``), which is a
backend choice, not a program bug.

**Host callbacks** — the chained window program must contain no host
round-trips (``infeed``/``outfeed``/``send``/``recv``/callback
custom-calls): one callback inside a ``chain_steps=N`` window reintroduces
the per-step host dispatch that chaining exists to remove, N times per
window.

All three run on CPU in seconds (abstract avals only — nothing executes),
which is what lets ``scripts/static_audit.py`` sit in verify.sh next to the
retrace/precision/perf gates.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable

import jax
import jax.numpy as jnp

from distributed_training_pytorch_tpu.profiling.categories import categorize
from distributed_training_pytorch_tpu.utils.hlo_flops import aval_bytes

__all__ = [
    "DonationReport",
    "PrecisionReport",
    "CallbackReport",
    "HloAuditReport",
    "parse_input_output_aliases",
    "count_entry_parameters",
    "audit_donation",
    "audit_precision_leaks",
    "audit_host_callbacks",
    "build_audit_engine",
    "run_hlo_audit",
]

_ALIAS_BLOCK_RE = re.compile(r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+),")
_ENTRY_LAYOUT_RE = re.compile(r"entry_computation_layout=\{\(")

# Host-callback markers in optimized HLO text. ``custom_call_target`` values
# are checked separately against _CALLBACK_TARGET_RE.
_CALLBACK_OPS = (" infeed(", " outfeed(", " send(", " recv(",
                 " send-done(", " recv-done(")
_CALLBACK_TARGET_RE = re.compile(
    r'custom_call_target="([^"]*(?:callback|host|py_func)[^"]*)"', re.IGNORECASE
)


_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) (?:\(.*\) -> .* )?\{\s*$")
_CALLEE_RE = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")


def computations(hlo_text: str) -> dict[str, list[str]]:
    """Instruction lines of an HLO module's text by the computation that holds
    them (the lowered module's ``name {`` headers and the compiled one's
    ``%name (params) -> type {`` alike)."""
    comps: dict[str, list[str]] = {}
    lines = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            lines = comps.setdefault(m.group(1), [])
        elif lines is not None and line.startswith(" "):
            lines.append(line)
    return comps


def called_from(comps: dict[str, list[str]], is_root: Callable[[str], bool]) -> set[str]:
    """Computations an instruction picked by ``is_root`` calls (a fusion, a
    ``while``'s body and condition, a ``call``'s target), and everything
    those call in turn."""
    reached = {c for lines in comps.values() for ln in lines if is_root(ln) for c in _CALLEE_RE.findall(ln)}
    frontier = list(reached)
    while frontier:
        for ln in comps.get(frontier.pop(), ()):
            for c in _CALLEE_RE.findall(ln):
                if c not in reached:
                    reached.add(c)
                    frontier.append(c)
    return reached


def parse_input_output_aliases(hlo_text: str) -> set[int]:
    """Parameter numbers that are input-output aliased (donated) in a
    compiled module's header. Empty set when the header carries no
    ``input_output_alias`` at all — the undonated-program signature."""
    m = _ALIAS_BLOCK_RE.search(hlo_text)
    if not m:
        return set()
    return {int(p) for p in _ALIAS_ENTRY_RE.findall(m.group(1))}


def count_entry_parameters(hlo_text: str) -> int:
    """Number of entry-computation parameters, from the
    ``entry_computation_layout={(...)->...}`` header — used to verify the
    jax-leaf <-> XLA-parameter index mapping is one-to-one before the
    donation report trusts it."""
    m = _ENTRY_LAYOUT_RE.search(hlo_text)
    if not m:
        raise ValueError("no entry_computation_layout header in HLO text")
    depth, count, any_tokens = 1, 0, False
    for ch in hlo_text[m.end():]:
        if ch in "([{<":
            depth += 1
        elif ch in ")]}>":
            depth -= 1
            if depth == 0:
                break
        elif ch == "," and depth == 1:
            count += 1
        elif not ch.isspace():
            any_tokens = True
    return count + 1 if any_tokens else 0


@dataclasses.dataclass
class DonationReport:
    """Per-leaf donation audit of one compiled program."""

    entries: list[dict]  # {path, role, shape, dtype, bytes, donated}
    label: str = ""

    @property
    def undonated(self) -> list[dict]:
        return [e for e in self.entries if e["must_donate"] and not e["donated"]]

    @property
    def undonated_bytes(self) -> float:
        return sum(e["bytes"] for e in self.undonated)

    @property
    def audited_bytes(self) -> float:
        return sum(e["bytes"] for e in self.entries if e["must_donate"])

    @property
    def donated_fraction(self) -> float:
        total = self.audited_bytes
        if not total:
            return 1.0
        return 1.0 - self.undonated_bytes / total

    @property
    def ok(self) -> bool:
        return not self.undonated

    def describe(self) -> str:
        head = (
            f"donation[{self.label}]: "
            f"{self.donated_fraction * 100:.1f}% of "
            f"{int(self.audited_bytes)} param+opt bytes aliased"
        )
        if self.ok:
            return head + " — OK"
        rows = "".join(
            f"\n    UNDONATED {e['path']} {e['dtype']}{list(e['shape'])} "
            f"({int(e['bytes'])} bytes)"
            for e in self.undonated
        )
        return head + f"; {int(self.undonated_bytes)} bytes undonated:" + rows


def _leaf_role(path_str: str) -> str:
    if ".params" in path_str:
        return "params"
    if ".opt_state" in path_str:
        return "opt_state"
    return "other"


def audit_donation(
    compiled,
    abstract_args: tuple,
    *,
    must_donate: Callable[[str], bool] | None = None,
    label: str = "",
) -> DonationReport:
    """Check that every leaf ``must_donate`` selects (default: params and
    optimizer state) is input-output aliased in ``compiled``.

    ``abstract_args`` is the full argument tuple the program was lowered
    with (e.g. ``(state, batch)``): its flattened leaves correspond 1:1, in
    order, to the module's entry parameters — asserted against the entry
    layout header before the mapping is trusted (jit's unused-argument
    pruning would silently shift the numbering otherwise).
    """
    from jax.tree_util import keystr, tree_flatten_with_path

    text = compiled.as_text()
    aliased = parse_input_output_aliases(text)
    leaves, _ = tree_flatten_with_path(abstract_args)
    n_params = count_entry_parameters(text)
    if n_params != len(leaves):
        raise ValueError(
            f"cannot map leaves to XLA parameters: program has {n_params} "
            f"entry parameters but the argument tree has {len(leaves)} "
            "leaves (an unused argument was pruned?) — the donation report "
            "would attribute aliases to the wrong leaves."
        )
    if must_donate is None:
        must_donate = lambda p: _leaf_role(p) in ("params", "opt_state")  # noqa: E731
    entries = []
    for index, (path, leaf) in enumerate(leaves):
        path_str = keystr(path)
        entries.append(
            {
                "path": path_str,
                "role": _leaf_role(path_str),
                "shape": tuple(leaf.shape),
                "dtype": str(leaf.dtype),
                "bytes": aval_bytes(leaf.shape, getattr(leaf, "dtype", None)),
                "donated": index in aliased,
                "must_donate": bool(must_donate(path_str)),
            }
        )
    return DonationReport(entries=entries, label=label)


@dataclasses.dataclass
class PrecisionReport:
    """fp32 MXU ops found in a low-precision program's lowered module."""

    leaks: list[dict]  # {op, category, result_type}
    policy: str = ""
    mxu_ops: int = 0  # total dot/conv ops inspected

    @property
    def ok(self) -> bool:
        # Zero MXU ops in a train step is not "clean" — it means the parse
        # (or the workload) regressed and the check would pass vacuously.
        return not self.leaks and self.mxu_ops > 0

    def describe(self) -> str:
        if not self.mxu_ops:
            return (
                f"precision[{self.policy}]: found NO dot/conv ops at all — "
                "parser or audit-workload regression (a train step always "
                "has matmuls); refusing a vacuous pass"
            )
        if self.ok:
            return (
                f"precision[{self.policy}]: no fp32 dot/conv among "
                f"{self.mxu_ops} MXU ops — OK"
            )
        rows = "".join(
            f"\n    LEAK {x['op']} -> {x['result_type']} ({x['category']})"
            for x in self.leaks
        )
        return (
            f"precision[{self.policy}]: {len(self.leaks)} fp32 MXU op(s) "
            "in a low-precision program:" + rows
        )


def audit_precision_leaks(lowered_text: str, *, policy: str = "") -> PrecisionReport:
    """Find fp32 ``dot``/``convolution`` ops in a lowered (StableHLO)
    module. Uses the shared profiling categorizer to decide which ops are
    MXU work, then checks each one's result element type."""
    leaks = []
    mxu_ops = 0
    matches = list(re.finditer(r"stablehlo\.([\w.]+)", lowered_text))
    for i, m in enumerate(matches):
        op = m.group(1)
        category = categorize(op)
        if category not in ("matmul", "convolution"):
            continue
        # The op's own type signature is the `-> tensor<...>` before the
        # next op begins; a signature past that belongs to someone else.
        bound = matches[i + 1].start() if i + 1 < len(matches) else len(lowered_text)
        sig = lowered_text.find("-> tensor<", m.end(), bound)
        if sig < 0:
            continue
        mxu_ops += 1
        end = lowered_text.find(">", sig + len("-> tensor<"))
        result = lowered_text[sig + len("-> tensor<"):end]
        dtype = result.rsplit("x", 1)[-1] if "x" in result else result
        if dtype == "f32":
            leaks.append({"op": op, "category": category, "result_type": result})
    return PrecisionReport(leaks=leaks, policy=policy, mxu_ops=mxu_ops)


@dataclasses.dataclass
class CallbackReport:
    """Host round-trip ops found in a compiled program."""

    hits: list[str]
    label: str = ""

    @property
    def ok(self) -> bool:
        return not self.hits

    def describe(self) -> str:
        if self.ok:
            return f"host-callbacks[{self.label}]: none — OK"
        return (
            f"host-callbacks[{self.label}]: {len(self.hits)} host "
            f"round-trip op(s) in the compiled program: {self.hits}"
        )


def audit_host_callbacks(hlo_text: str, *, label: str = "") -> CallbackReport:
    hits = []
    for marker in _CALLBACK_OPS:
        if marker in hlo_text:
            hits.append(marker.strip(" ("))
    hits.extend(_CALLBACK_TARGET_RE.findall(hlo_text))
    return CallbackReport(hits=hits, label=label)


# -- the audited workload ---------------------------------------------------


def build_audit_engine(precision=None, mesh=None, *, sharding_rules=None,
                       fsdp_min_size: int = 2**18):
    """A small conv+dense workload through the real :class:`TrainEngine` —
    the same shape of fixture the perf gate times (CPU-viable, compiles in
    seconds), here only *lowered*, never run. Returns ``(engine,
    abstract_state, abstract_batch)``; nothing touches a device.
    ``sharding_rules``/``fsdp_min_size`` configure the sharded-audit
    variants (a low ``fsdp_min_size`` so the fixture's small leaves really
    shard — a "sharded" audit of a fully replicated program would be a
    vacuous pass)."""
    import optax
    from flax import linen as nn

    from distributed_training_pytorch_tpu.ops import cross_entropy_loss
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
    from distributed_training_pytorch_tpu.train import (
        TrainEngine,
        make_supervised_loss,
    )
    from distributed_training_pytorch_tpu.train.state import TrainState

    class AuditNet(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = nn.relu(nn.Conv(8, (3, 3))(x))
            x = x.reshape(x.shape[0], -1)
            return nn.Dense(10)(x)

    def criterion(logits, batch):
        loss = cross_entropy_loss(logits, batch["label"])
        return loss, {"loss": loss}

    model = AuditNet()
    optimizer = optax.sgd(0.05, momentum=0.9)
    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optimizer,
        mesh if mesh is not None else mesh_lib.create_mesh(),
        precision=precision,
        sharding_rules=sharding_rules,
        fsdp_min_size=fsdp_min_size,
    )
    batch_size = 8 * max(1, jax.device_count())

    def make_state(rng):
        variables = model.init(rng, jnp.zeros((1, 8, 8, 3), jnp.float32))
        params = variables.pop("params")
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            model_state=dict(variables),
            rng=rng,
            loss_scale=engine.initial_loss_scale,
        )

    abstract_state = jax.eval_shape(make_state, jax.random.key(0))
    abstract_batch = {
        "image": jax.ShapeDtypeStruct((batch_size, 8, 8, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((batch_size,), jnp.int32),
    }
    return engine, abstract_state, abstract_batch


def _stack_abstract(batch: dict, length: int) -> dict:
    # Shared stacking rule (train.engine): the audited window shape is the
    # dispatched one by construction.
    from distributed_training_pytorch_tpu.train.engine import stack_chain_batch

    return stack_chain_batch(batch, length)


@dataclasses.dataclass
class HloAuditReport:
    single: DonationReport
    chained: DonationReport
    precision: PrecisionReport
    callbacks: CallbackReport
    # SPMD-partitioned twins (ISSUE 10): the same invariants on programs
    # whose state is REALLY fsdp/tensor-sharded. None = skipped (fewer than
    # 8 devices — the forced-host count scripts/static_audit.py sets up);
    # the `sharded` flag distinguishes "ran and passed" from "not run".
    sharded_single: "DonationReport | None" = None
    sharded_chained: "DonationReport | None" = None
    sharded_precision: "PrecisionReport | None" = None
    injected: bool = False

    @property
    def sharded(self) -> bool:
        return self.sharded_single is not None

    def _parts(self):
        parts = [self.single, self.chained, self.precision, self.callbacks]
        parts += [
            p
            for p in (self.sharded_single, self.sharded_chained, self.sharded_precision)
            if p is not None
        ]
        return parts

    @property
    def ok(self) -> bool:
        return all(part.ok for part in self._parts())

    def describe(self) -> str:
        lines = ["  " + part.describe() for part in self._parts()]
        if not self.sharded:
            lines.append(
                "  sharded audit: SKIPPED (needs >= 8 devices for the "
                "data=2/fsdp=2/tensor=2 mesh; static_audit forces 8 host "
                "devices, so the verify gate always runs it)"
            )
        return "\n".join(lines)

    def to_fields(self) -> dict:
        """Flat JSON-safe summary for the ``static_audit`` telemetry event."""
        fields = {
            "undonated_bytes_single": self.single.undonated_bytes,
            "undonated_bytes_chained": self.chained.undonated_bytes,
            "donated_fraction_single": self.single.donated_fraction,
            "donated_fraction_chained": self.chained.donated_fraction,
            "precision_leaks": len(self.precision.leaks),
            "host_callbacks": len(self.callbacks.hits),
            "sharded": self.sharded,
            "injected": self.injected,
            "passed": self.ok,
        }
        if self.sharded:
            fields["donated_fraction_sharded_single"] = (
                self.sharded_single.donated_fraction
            )
            fields["donated_fraction_sharded_chained"] = (
                self.sharded_chained.donated_fraction
            )
            fields["sharded_precision_leaks"] = len(self.sharded_precision.leaks)
        return fields


def _audit_mesh():
    """The sharded-audit mesh: data=2/fsdp=2/tensor=2 over the first 8
    devices — every sharding mode the Trainer hot path supports, in one
    program. None when the platform has fewer than 8 devices (the audit is
    then skipped and says so; ``scripts/static_audit.py`` forces an 8-device
    host platform so the verify gate always exercises it)."""
    if jax.device_count() < 8:
        return None
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

    return mesh_lib.create_mesh(
        {"data": 2, "fsdp": 2, "tensor": 2}, devices=jax.devices()[:8]
    )


# Explicit TP rule for the audit fixture's Dense head + a low FSDP cutoff:
# the fixture's leaves are tiny, and a "sharded" audit of a program whose
# every leaf fell back to replicated would pass vacuously. test_analysis
# pins that the audited state really carries fsdp AND tensor specs.
_AUDIT_SHARDING_RULES = (("Dense_0.*kernel", jax.sharding.PartitionSpec(None, "tensor")),)
_AUDIT_FSDP_MIN_SIZE = 128


def run_hlo_audit(chain_steps: int = 4, *, inject_violation: bool = False) -> HloAuditReport:
    """Lower the real single-step and chained train programs on abstract
    avals (via ``TrainEngine.compile_step_probe``) and audit donation, then
    audit a bf16-policy lowering for precision leaks and the chained
    program for host callbacks. With >= 8 devices the same donation +
    precision invariants are audited on SPMD-partitioned twins — a
    data=2/fsdp=2/tensor=2 mesh with genuinely sharded state — because
    donation under partitioning is a separate property (aliasing must
    survive SPMD's parameter rewriting) and ISSUE 10's sharded hot path
    depends on it.

    ``inject_violation=True`` is the self-test seam (the perf gate's
    ``--inject-slowdown`` analog): the donation audits — sharded ones
    included — run against probes lowered WITHOUT donation, structurally
    the exact bug the audit exists to catch, and the report must come back
    failing.
    """
    donate = not inject_violation
    engine, state, batch = build_audit_engine()
    single = engine.compile_step_probe(state, batch, donate=donate)
    single_report = audit_donation(single, (state, batch), label="single-step")
    window = _stack_abstract(batch, chain_steps)
    chained = engine.compile_step_probe(
        state, window, donate=donate, chain_length=chain_steps
    )
    chained_report = audit_donation(
        chained, (state, window), label=f"chained x{chain_steps}"
    )
    callback_report = audit_host_callbacks(
        chained.as_text(), label=f"chained x{chain_steps}"
    )
    bf16_engine, bf16_state, bf16_batch = build_audit_engine(precision="bf16")
    lowered = bf16_engine.lower_step_probe(bf16_state, bf16_batch, donate=donate)
    precision_report = audit_precision_leaks(lowered.as_text(), policy="bf16")
    sharded_single = sharded_chained = sharded_precision = None
    mesh = _audit_mesh()
    if mesh is not None:
        sh_engine, sh_state, sh_batch = build_audit_engine(
            mesh=mesh,
            sharding_rules=_AUDIT_SHARDING_RULES,
            fsdp_min_size=_AUDIT_FSDP_MIN_SIZE,
        )
        sh_compiled = sh_engine.compile_step_probe(sh_state, sh_batch, donate=donate)
        sharded_single = audit_donation(
            sh_compiled, (sh_state, sh_batch), label="sharded single-step"
        )
        sh_window = _stack_abstract(sh_batch, chain_steps)
        sh_chained = sh_engine.compile_step_probe(
            sh_state, sh_window, donate=donate, chain_length=chain_steps
        )
        sharded_chained = audit_donation(
            sh_chained, (sh_state, sh_window),
            label=f"sharded chained x{chain_steps}",
        )
        sh_bf16_engine, sh_bf16_state, sh_bf16_batch = build_audit_engine(
            precision="bf16",
            mesh=mesh,
            sharding_rules=_AUDIT_SHARDING_RULES,
            fsdp_min_size=_AUDIT_FSDP_MIN_SIZE,
        )
        sh_lowered = sh_bf16_engine.lower_step_probe(
            sh_bf16_state, sh_bf16_batch, donate=donate
        )
        sharded_precision = audit_precision_leaks(
            sh_lowered.as_text(), policy="bf16 sharded"
        )
    return HloAuditReport(
        single=single_report,
        chained=chained_report,
        precision=precision_report,
        callbacks=callback_report,
        sharded_single=sharded_single,
        sharded_chained=sharded_chained,
        sharded_precision=sharded_precision,
        injected=inject_violation,
    )
