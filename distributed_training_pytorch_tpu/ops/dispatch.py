"""Kernel dispatch policy — the single decision point for the Pallas hot paths.

Every model that *could* run a fused Pallas kernel (flash attention for
ViT/TransformerLM, the fused ``conv1x1_bn_act`` GEMM+epilogue for
ResNet/ConvNeXt) resolves which path it actually takes through this module,
so the policy lives in exactly one place and every resolution is observable.

The knob convention (the ``telemetry=None`` pillar applied to kernels):

* Each model takes a ``pallas: Optional[bool] = None`` constructor knob.
  ``True`` forces the fused kernels, ``False`` forces the plain XLA paths,
  and ``None`` (the default) means *auto* — the per-model policy below,
  which is exactly the historical behavior, so an unset knob is
  bit-identical with the pre-dispatch program (test-enforced in
  tests/test_dispatch.py).
* The library never reads environment variables.  Example entries read the
  ``PALLAS`` env via :func:`pallas_from_env` (the DTYPE/CHAIN_STEPS/MESH
  convention) and pass the result down as the constructor knob.

Per-model auto policies (who gets a kernel when the knob is ``None``):

=============  =======================  =========================================
model          op                       auto resolution
=============  =======================  =========================================
vit            attention                historical ``use_flash`` tri-state
                                        (default off; ViTB16 passes auto →
                                        flash on TPU when ``T >= 512``)
transformer_lm attention                historical ``attention_impl`` string
                                        (default "auto" → flash on TPU)
resnet         conv1x1_bn_act           **off** — not measured on today's chip
                                        (an earlier round: slower in-step);
                                        also changes the param tree, so it is
                                        opt-in for fresh inits only
convnext       dense_gelu epilogue      **off** — opt in via ``pallas=True`` /
                                        ``PALLAS=1`` (autotuner evidence,
                                        docs/performance.md "Autotuning")
vgg16          (none)                   no fused-kernel coverage (3x3 convs);
                                        every resolution lands on plain
hybrid_lm      attention                flash on TPU when ``T >= 512``
hybrid_lm      ssd                      the Pallas scan (``ops/ssd.py:ssd_scan``)
                                        on TPU where the shape tiles, else the
                                        ``jax.numpy`` chunked form
nemotron_h     attention, ssd           as hybrid_lm (the same modules)
nemotron_h     moe_experts              no choice yet: ``jax.lax.ragged_dot``,
                                        recorded by the layer itself
                                        (``parallel/moe.py:HeldExpertsMlp``)
nemotron_h     moe_rows                 the layer's four row movements: the
                                        kernels of ``ops/moe_rows.py`` (work
                                        follows the live pairs) on TPU where
                                        the shape tiles and one device holds
                                        the tokens, else the ``jax.numpy``
                                        gathers over the whole buffer
=============  =======================  =========================================

Observability (the silent-fall-through fix): each resolution is recorded as
a one-time ``kernel_dispatch`` decision — ``(model, op, path, reason)``
deduplicated per process — and forwarded to an installed event sink
(normally ``EventLog.emit``, installed by the Trainer for the duration of a
run).  Decisions recorded before a sink exists are buffered and flushed on
install, so the resolutions made while building the model still land in the
run's event log.  Recording happens in host Python at trace/build time and
never touches the compiled program: ``PALLAS=0`` / ``pallas=False`` (and the
unset default) reproduce the historical executable bit-exactly.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "pallas_from_env",
    "resolve",
    "attention_fn",
    "lm_attention_impl",
    "ssd_fn",
    "moe_rows_tile",
    "conv1x1_policy",
    "record",
    "records",
    "set_event_sink",
    "clear_event_sink",
    "reset",
]

_EVENT = "kernel_dispatch"

_lock = threading.Lock()
_seen: Dict[Tuple[str, str, str, str], Dict[str, Any]] = {}
_pending: List[Dict[str, Any]] = []
_sink: Optional[Callable[..., Any]] = None


def pallas_from_env(env: Optional[dict] = None, *, default: Optional[bool] = None):
    """Parse the ``PALLAS`` env knob: ``"1"`` → True, ``"0"`` → False,
    unset/empty → ``default`` (normally ``None`` = per-model auto).

    Entry-level only — library code takes the returned value as an explicit
    constructor knob and never reads the environment itself.
    """
    if env is None:
        import os

        env = os.environ
    raw = env.get("PALLAS", "")
    if raw == "":
        return default
    if raw not in ("0", "1"):
        raise ValueError(f"PALLAS must be '0' or '1' (got {raw!r})")
    return raw == "1"


def resolve(knob: Optional[bool], fallback):
    """Three-state resolution: an explicit ``pallas=`` knob wins; ``None``
    defers to the model's historical/legacy control (``fallback``)."""
    return fallback if knob is None else knob


# ---------------------------------------------------------------------------
# decision recording
# ---------------------------------------------------------------------------


def record(model: str, op: str, path: str, *, reason: str = "", **detail) -> bool:
    """Record one dispatch decision; dedup on ``(model, op, path, reason)``.

    Returns True when this was the first time the decision was seen (and so
    was emitted/buffered), False for a dedup hit.  Host-side only — safe to
    call from inside a traced ``__call__`` (it runs at trace time).
    """
    key = (model, op, path, reason)
    fields = {"model": model, "op": op, "path": path, "reason": reason}
    fields.update(detail)
    with _lock:
        if key in _seen:
            return False
        _seen[key] = fields
        sink = _sink
        if sink is None:
            _pending.append(fields)
            return True
    # Emit outside the lock: the sink (EventLog.emit) takes its own lock.
    sink(_EVENT, **fields)
    return True


def records() -> List[Dict[str, Any]]:
    """Snapshot of every decision recorded so far (tests / doctor)."""
    with _lock:
        return [dict(f) for f in _seen.values()]


def set_event_sink(emit: Callable[..., Any]) -> None:
    """Install ``emit(event, **fields)`` (normally ``EventLog.emit``) and
    flush any decisions buffered before a sink existed."""
    global _sink
    with _lock:
        _sink = emit
        pending, _pending[:] = list(_pending), []
    for fields in pending:
        emit(_EVENT, **fields)


def clear_event_sink() -> None:
    """Uninstall the sink (Trainer teardown).  Dedup state is kept — the
    one-time contract is per process, not per run."""
    global _sink
    with _lock:
        _sink = None


def reset() -> None:
    """Testing hook: forget all decisions, buffers, and the sink."""
    global _sink
    with _lock:
        _seen.clear()
        _pending[:] = []
        _sink = None


# ---------------------------------------------------------------------------
# attention (vit / transformer_lm)
# ---------------------------------------------------------------------------


def attention_fn(
    model: str,
    use_flash: Optional[bool],
    *,
    causal: bool = False,
    **kwargs,
):
    """Resolve the attention path for ``model`` and return an attention
    callable, or ``None`` meaning *use the caller's historical plain path*.

    ``use_flash`` is the already-resolved tri-state (the model's ``pallas``
    knob overriding its legacy ``use_flash``/``attention_impl`` control):
    ``False`` → plain, ``True`` → flash for every length, ``None`` → auto
    (flash on TPU for ``T >= FLASH_MIN_SEQ_LEN``, plain elsewhere).

    The returned callable records which path each *actual* sequence length
    resolved to — including the silent below-``FLASH_MIN_SEQ_LEN``
    fall-through that previously dropped to plain with no signal.
    ``kwargs`` (block_q/block_k/interpret/…) pass through to
    :func:`~distributed_training_pytorch_tpu.ops.pallas.make_attention_fn`.
    """
    if use_flash is False:
        record(model, "attention", "plain", reason="pallas=False")
        return None
    import jax

    if use_flash is None and jax.default_backend() != "tpu":
        record(
            model,
            "attention",
            "plain",
            reason=f"auto: backend={jax.default_backend()} (flash is TPU-default only)",
        )
        return None

    from .pallas import FLASH_MIN_SEQ_LEN, flash_block_plan, make_attention_fn

    min_seq_len = 1 if use_flash is True else FLASH_MIN_SEQ_LEN
    inner = make_attention_fn(causal=causal, min_seq_len=min_seq_len, **kwargs)

    def dispatching_attention(q, k, v, valid_len=None):
        seq = q.shape[1]
        if seq < min_seq_len:
            # The formerly-silent fall-through: make_attention_fn drops to
            # the plain path below min_seq_len.  Same routing — now named.
            record(
                model,
                "attention",
                "plain",
                reason=f"T={seq} < FLASH_MIN_SEQ_LEN={min_seq_len}",
                seq_len=seq,
            )
        else:
            record(
                model,
                "attention",
                "flash",
                reason="pallas=True (forced)" if use_flash is True else f"auto: T={seq} >= {min_seq_len}",
                seq_len=seq,
                # Static at trace time: the forward's block shape, the
                # sub-tile size a one-block causal call is walked in, how many
                # of a sequence's block pairs (or sub-tiles) it visits (causal
                # skips the rest), and which backward these shapes get at
                # which block shape and sub-tile size.
                **flash_block_plan(
                    seq, k.shape[1], causal, kwargs.get("block_q"), kwargs.get("block_k")
                ),
            )
        if valid_len is None:
            return inner(q, k, v)
        return inner(q, k, v, valid_len=valid_len)

    return dispatching_attention


def lm_attention_impl(attention_impl: str, pallas: Optional[bool]) -> str:
    """Map TransformerLM's ``pallas`` knob onto its legacy ``attention_impl``
    string: True → "flash", False → "plain", None → keep the legacy value
    (the historical program)."""
    if pallas is True:
        return "flash"
    if pallas is False:
        return "plain"
    return attention_impl


# ---------------------------------------------------------------------------
# the Mamba-2 scan (hybrid_lm)
# ---------------------------------------------------------------------------


def ssd_fn(model: str, pallas: Optional[bool]):
    """Resolve the state-space scan's path for ``model``: a callable with
    :func:`~distributed_training_pytorch_tpu.ops.ssd.ssd_chunked`'s signature
    that, at each call's shapes, runs the Pallas kernels
    (:func:`~distributed_training_pytorch_tpu.ops.ssd.ssd_scan`) or the
    ``jax.numpy`` chunked form, and records which and why.

    ``pallas=False`` -> chunked. ``None`` -> the kernels on a TPU where the
    shape tiles (``ssd_tiles``), chunked elsewhere. ``True`` -> the kernels
    wherever the shape tiles, interpreted off the chip (parity tests); a
    shape the kernels do not take still falls to chunked, with the reason."""
    import jax

    from .ssd import scan_groups, ssd_chunked, ssd_scan, ssd_tiles

    def scan(x, dt, a, b, c, *, chunk, dtype=None):
        (_, t, h, p), n = x.shape, b.shape[-1]
        groups = scan_groups(b)
        backend = jax.default_backend()
        shape = f"chunk {chunk}, {h} heads of {p}, d_state {n}" + (f", {groups} groups" if groups > 1 else "")
        refused = ssd_tiles(chunk, h, p, n, t, groups)
        if pallas is False:
            path, reason = "chunked", "pallas=False"
        elif pallas is None and backend != "tpu":
            path, reason = "chunked", f"auto: backend={backend} (the scan kernels are TPU-default only)"
        elif refused:
            path, reason = "chunked", f"backend={backend}: {refused} ({shape})"
        else:
            path = "pallas"
            reason = f"pallas=True (forced), backend={backend}: {shape}" if pallas else f"auto: backend={backend}, {shape} tiles"
        record(model, "ssd", path, reason=reason)
        return (ssd_scan if path == "pallas" else ssd_chunked)(x, dt, a, b, c, chunk=chunk, dtype=dtype)

    return scan


# ---------------------------------------------------------------------------
# the routed expert layer's row movements (nemotron_h)
# ---------------------------------------------------------------------------

MOE_ROWS_BACKENDS = ("tpu",)  # where the row kernels are the default (a test that wants them interpreted adds its own)


def moe_rows_tile(model: str, tokens: int, k: int, width: int, dtype) -> Optional[int]:
    """Resolve the form of ``parallel/moe.py:HeldExpertsMlp``'s four row
    movements (dispatch, combine and their transposes) and record it: the
    tokens a grid step of the kernels of ``ops/moe_rows.py``, whose work
    follows the live pairs, or None for the ``jax.numpy`` form over all
    ``tokens · k`` pairs. The kernels on a TPU where the shape tiles
    (``moe_rows.rows_refused``) and the tokens are on one device; no knob:
    the layer has one answer a shape and a platform."""
    import jax

    from .moe_rows import rows_refused, rows_tile

    backend = jax.default_backend()
    mesh = jax.sharding.get_abstract_mesh()
    refused = rows_refused(tokens, k, width, dtype)
    if backend not in MOE_ROWS_BACKENDS:
        path, reason = "gather", f"auto: backend={backend} (the row kernels are TPU-default only)"
    elif refused:
        path, reason = "gather", f"backend={backend}: {refused}"
    elif mesh.axis_names and mesh.size > 1 and not mesh.manual_axes:
        path, reason = "gather", f"backend={backend}: a mesh of {mesh.size} devices may split the tokens, which the kernels take whole"
    else:
        path, reason = "pallas", (f"auto: backend={backend}, {tokens} tokens x top-{k} of width {width} tile: "
                                  "work follows the live pairs (moe.pairs_local), not the buffer")
    record(model, "moe_rows", path, reason=reason)
    return rows_tile(tokens) if path == "pallas" else None


# ---------------------------------------------------------------------------
# fused conv1x1 / dense epilogues (resnet / convnext)
# ---------------------------------------------------------------------------


def conv1x1_policy(
    model: str,
    pallas: Optional[bool],
    *,
    legacy: bool = False,
    op: str = "conv1x1_bn_act",
    auto_off_reason: str = "auto: off until measured faster end-to-end on today's chip — opt in with pallas=True",
) -> bool:
    """Resolve + record the fused-GEMM-epilogue policy for ``model``.

    Auto (``pallas=None`` and ``legacy`` False) stays **off**: the fused
    1x1-conv path is not measured on today's chip (an earlier round found it
    slower end-to-end than XLA's own fusions), so promotion is
    evidence-gated — an explicit ``pallas=True`` flips it, never a silent
    default.
    """
    on = resolve(pallas, legacy)
    if on:
        reason = "pallas=True" if pallas is True else "legacy knob"
        record(model, op, "pallas", reason=reason)
    else:
        reason = "pallas=False" if pallas is False else auto_off_reason
        record(model, op, "plain", reason=reason)
    return bool(on)
