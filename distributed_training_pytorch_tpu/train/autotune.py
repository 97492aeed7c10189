"""XLA-flag / schedule autotuner core — the candidate-measurement and
ranking library behind ``scripts/autotune.py`` (ISSUE 17).

The measurement stack can *detect* a stuck bench line
(``telemetry.history.detect_flat_streaks``); this module is the instrument
meant to *move* it. The shape is deliberate: every piece reuses an existing,
test-enforced implementation rather than growing a private twin —

* **Timing** — ``time_chained`` is the two-length-differencing scan-chain
  timer from ``scripts/resnet_pallas_probe.py`` (a constant per-dispatch
  cost cancels exactly), generalized to any
  ``f(*args)`` and hosted here so the probe imports it (test-enforced: the
  probe defines no private copy). ``measure_chained_step`` applies the same
  differencing to the REAL chained train-step executable
  (``TrainEngine.compile_chained_train_steps``) — candidates are ranked on
  the program that ships, not a proxy kernel.
* **Attribution** — every candidate-vs-baseline delta goes through
  ``profiling.diff.attribute_entry_delta`` (the run_compare/perf_gate
  implementation), so a winning config arrives with the same per-category
  evidence a regression would.
* **Refusal** — the PR 14 rule, adapted for deliberate sweeps: a candidate
  whose provenance CONFIG facets differ from the baseline's on any key it
  did NOT declare as swept (its ``knobs``) is refused, not ranked. Sweeping
  ``chain_steps`` legitimately changes that facet; a silently different
  ``dtype`` makes the comparison meaningless and must not produce a number.
* **Ranking** — lowest ``step_ms`` wins, but a win is *kept* only when it
  beats the baseline by more than ``FLAT_REL_TOL`` (the flat-streak
  detector's band): a "win" inside the noise band would re-flatten the bench
  line the next round and teach the tuner to chase noise.

``emit_tuned`` writes the sweep report (``TUNED.json``). Nothing reads a
report back into a run: a winner becomes a default only through a PR whose
numbers are in the driver's ledger for every cell (ROADMAP S2) — an XLA flag
recorded under one jax/libtpu must never be exported into a process running
another (XLA aborts on a flag it does not know).
"""

from __future__ import annotations

import functools
import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

# A kept win must clear the flat-streak detector's band, or the next bench
# round lands back inside the streak it claims to end.
from distributed_training_pytorch_tpu.telemetry.history import FLAT_REL_TOL

__all__ = [
    "Candidate",
    "emit_tuned",
    "load_tuned",
    "measure_chained_step",
    "rank_candidates",
    "time_chained",
]

@dataclass
class Candidate:
    """One point in the declared sweep space.

    ``knobs`` is the candidate grammar (docs/performance.md "Autotuning"):

    * ``xla_flags`` — ``"--xla_..."`` string, applied per-compile via
      ``train.engine.xla_flag_options`` (never by mutating global XLA_FLAGS)
    * ``chain_steps`` — on-device steps per dispatch (lax.scan chain length)
    * ``batch`` / ``accum_steps`` — microbatch/accumulation shape
    * ``pallas`` — the unified kernel-policy knob (ops/dispatch.py)
    * ``block_rows`` — Pallas kernel tile knob (ops/pallas.py)

    Every key present in ``knobs`` is a *declared* swept facet: provenance
    disagreement on exactly those keys is expected and allowed; any other
    disagreement refuses the comparison (``rank_candidates``).
    """

    name: str
    knobs: dict = field(default_factory=dict)
    note: str = ""


def time_chained(f: Callable, *args, steps: int = 20, windows: int = 4,
                 perturb_arg: int = 1) -> float:
    """Per-call seconds for ``f(*args)`` by TWO-LENGTH DIFFERENCING: the
    per-dispatch host latency is a
    constant per window, so time a short (``steps``) and a long
    (``5 * steps``) chain of the same scan body and divide the time
    difference by the extra trips; the dispatch constant cancels exactly.
    Best of ``windows`` windows per length.

    The scan body perturbs ``args[perturb_arg]`` by the carried output
    statistic (a data-dependent ~1e-30 scalar), so no iteration is
    loop-invariant — blocks hoisting and CSE without changing the math.
    This is the one timing implementation shared by the autotuner and
    ``scripts/resnet_pallas_probe.py`` (AST-test-enforced: the probe keeps
    no private copy).
    """
    import jax
    import jax.numpy as jnp

    def body(c, _):
        perturbed = list(args)
        a = args[perturb_arg]
        perturbed[perturb_arg] = (a.astype(jnp.float32) * (1.0 + c)).astype(a.dtype)
        out = f(*perturbed)
        # tiny, data-dependent carry: blocks loop-invariant hoisting and CSE
        return jnp.ravel(out)[:8].astype(jnp.float32).sum() * 1e-30, None

    @functools.partial(jax.jit, static_argnums=0)
    def chained(length, *call_args):
        c, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None, length=length)
        return c

    short, long_ = int(steps), 5 * int(steps)
    times = {}
    for length in (short, long_):
        _ = float(chained(length, *args))  # compile + warm (scalar sync)
        best = float("inf")
        for _w in range(int(windows)):
            t0 = time.perf_counter()
            _ = float(chained(length, *args))
            best = min(best, time.perf_counter() - t0)
        times[length] = best
    return (times[long_] - times[short]) / (long_ - short)


def measure_chained_step(
    engine,
    state,
    batch,
    *,
    chain_steps: int = 4,
    windows: int = 3,
    compiler_options: Mapping[str, str] | None = None,
    categories: bool = True,
) -> tuple[dict, Any]:
    """Measure one candidate's per-step milliseconds on the REAL chained
    train-step executable, with perf_gate-style category capture.

    Two-length differencing at the executable level: compile the
    ``chain_steps`` and ``5 * chain_steps`` chains (same avals, same
    ``compiler_options``), warm both, best-of-``windows`` each, and divide
    the window-time difference by the extra steps — per-dispatch host
    latency cancels, leaving sustained device step time. The state is
    re-threaded through every call (donation-safe); the returned state is
    the post-measurement one.

    Returns ``(measurement, state)`` where measurement carries ``step_ms``
    plus the ``categories`` fractions of one traced extra window (degrading
    to no categories on any capture failure, exactly like perf_gate) — the
    two keys ``profiling.diff.attribute_entry_delta`` needs to pre-attribute
    any delta against this measurement.
    """
    import jax

    short, long_ = int(chain_steps), 5 * int(chain_steps)
    opts = dict(compiler_options) if compiler_options else None
    st = state
    times = {}
    compiled_long = None
    for length in (short, long_):
        compiled = engine.compile_chained_train_steps(
            st, batch, length, compiler_options=opts
        )
        if length == long_:
            compiled_long = compiled
        st, metrics = compiled(st, batch)  # warm (first dispatch pays setup)
        jax.block_until_ready(metrics)
        best = float("inf")
        for _w in range(int(windows)):
            t0 = time.perf_counter()
            st, metrics = compiled(st, batch)
            jax.block_until_ready(metrics)
            best = min(best, time.perf_counter() - t0)
        times[length] = best
    per_step_s = (times[long_] - times[short]) / (long_ - short)
    measurement = {
        "step_ms": round(per_step_s * 1e3, 4),
        "chain_steps": int(chain_steps),
        "windows": int(windows),
    }
    if categories:
        # Category capture (perf_gate idiom): trace ONE extra long window
        # AFTER the timed ones (the trace gates nothing it measures) and
        # attach StepProfile category fractions; degrade gracefully.
        import shutil
        import sys
        import tempfile

        from distributed_training_pytorch_tpu import profiling as profiling_lib

        prof_dir = tempfile.mkdtemp(prefix="autotune_prof_")
        try:
            with profiling_lib.trace(prof_dir):
                st, metrics = compiled_long(st, batch)
                jax.block_until_ready(metrics)
            prof = profiling_lib.analyze_trace(prof_dir, steps=long_)
            measurement["categories"] = {
                k: round(v, 4) for k, v in prof.categories.items() if v
            }
        except (ValueError, FileNotFoundError, OSError, RuntimeError) as e:
            print(f"autotune: category capture failed ({e}) — this "
                  "candidate's delta will be unattributed", file=sys.stderr)
        finally:
            shutil.rmtree(prof_dir, ignore_errors=True)
    return measurement, st


def rank_candidates(
    baseline: dict,
    results: list[dict],
    *,
    metric: str = "step_ms",
    rel_margin: float = FLAT_REL_TOL,
) -> dict:
    """Rank measured candidates against the baseline; refuse unsound ones.

    ``baseline``/``results[i]`` are ``{"name", "knobs", "measurement"}``
    dicts where measurement carries ``metric`` (+ optionally ``categories``
    and ``provenance`` from ``telemetry.provenance.provenance_fields``).

    * **Refusal** (the PR 14 rule, sweep-adapted): provenance CONFIG keys
      that differ from the baseline and are NOT declared in the candidate's
      ``knobs`` make the comparison meaningless — the candidate lands in
      ``refused`` with the offending keys named, never in the ranking.
    * **Ranking**: accepted candidates sort by ``metric`` ascending; each
      carries its delta vs baseline and the per-category attribution rows
      (``profiling.diff.attribute_entry_delta`` — None when either side
      lacks categories).
    * **Keep rule**: the best candidate becomes ``winner`` only if it beats
      the baseline by more than ``rel_margin`` (default: the flat-streak
      band ``FLAT_REL_TOL``); otherwise ``kept`` is False and the baseline
      config stands — a sub-noise "win" is reverted, not shipped.
    """
    from distributed_training_pytorch_tpu.profiling import diff as diff_lib
    from distributed_training_pytorch_tpu.telemetry import provenance

    base_meas = baseline["measurement"]
    base_val = float(base_meas[metric])
    base_prov = base_meas.get("provenance") or {}
    ranked: list[dict] = []
    refused: list[dict] = []
    for r in results:
        meas = r["measurement"]
        swept = set(r.get("knobs") or {})
        prov = meas.get("provenance") or {}
        undeclared = [
            k for k in provenance.differing_keys(base_prov, prov)
            if k not in swept
        ]
        if undeclared:
            refused.append({
                "name": r["name"],
                "differing_keys": undeclared,
                "reason": "provenance facets differ on keys the candidate "
                          "did not declare as swept — comparison refused "
                          "(PR 14 rule)",
            })
            continue
        rows = diff_lib.attribute_entry_delta(base_meas, meas, metric=metric)
        ranked.append({
            "name": r["name"],
            "knobs": dict(r.get("knobs") or {}),
            "note": r.get("note", ""),
            "measurement": meas,
            "delta_ms": round(float(meas[metric]) - base_val, 4),
            "attribution": [row.to_dict() for row in rows] if rows else None,
            "attribution_text": (
                diff_lib.describe_rows(rows) if rows else ""
            ),
        })
    ranked.sort(key=lambda e: float(e["measurement"][metric]))
    kept = bool(ranked) and (
        float(ranked[0]["measurement"][metric]) < base_val * (1.0 - rel_margin)
    )
    return {
        "schema": 1,
        "metric": metric,
        "rel_margin": rel_margin,
        "baseline": baseline,
        "ranked": ranked,
        "refused": refused,
        "kept": kept,
        "winner": ranked[0] if kept else None,
    }


def emit_tuned(path: str, report: dict) -> dict:
    """Write the sweep report as a ``TUNED.json`` artifact.

    The file IS the evidence: baseline + every ranked candidate with its
    delta and per-category attribution + every refusal with the offending
    provenance keys + the keep/revert verdict. Reviewing the TUNED.json
    diff reviews the perf claim (same ritual as PERF_BASELINE.json).

    Rank 0 owns the file (utils/logger convention) — a multi-host sweep
    measures everywhere but writes once.
    """
    import jax

    if jax.process_index() == 0:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=False)
            f.write("\n")
    return report


def load_tuned(path: str) -> dict | None:
    """Load an emitted TUNED.json; None when absent/unreadable."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (FileNotFoundError, ValueError):
        return None
