#!/usr/bin/env python
"""Run comparison — ranked A/B attribution between two runs (ISSUE 14).

The per-run stack can explain one run exhaustively (goodput, StepProfile,
memory classes, comm inventory, doctor); this CLI answers the question the
ROADMAP actually asks: *why did step_ms change (or refuse to change)
between two runs?* It takes two artifacts, auto-detects their kind, and
prints a doctor-style ranked attribution report — every verdict row
carrying evidence refs (trace paths, event-log line numbers) — through the
ONE delta-attribution implementation (``profiling.diff``; perf_gate's FAIL
diagnosis uses the same code, test-enforced).

Inputs (both sides must be the same kind; ``--kind`` overrides detection)::

    python scripts/run_compare.py A.xplane.pb B.xplane.pb   # profile captures
    python scripts/run_compare.py tracedirA/ tracedirB/     #   (or trace dirs)
    python scripts/run_compare.py run_a/ run_b/             # Trainer run dirs
    python scripts/run_compare.py before.json after.json    # bench entries
    python scripts/run_compare.py --kind hlo a.hlo b.hlo    # optimized-HLO texts

* **profile vs profile** — ``profiling.diff.diff_profiles``: ranked
  per-category step-delta rows (fractions of delta sum to 1), matched
  top-op deltas with new/removed ops named, roofline shifts
  (memory->compute is the Pallas-win signature; ``--ridge`` arms it).
* **run dir vs run dir** — per-step goodput-bucket deltas (the same bucket
  wall the doctor reads), plus the profile-category diff when both runs
  carried a ``profile_capture``; evidence rows cite event-log lines.
* **bench vs bench** — headline metric deltas (step_ms, value, mfu family),
  with category attribution when both entries carry ``BENCH_PROFILE=1``
  fields.
* **hlo vs hlo** — ``analysis.diff``: op-category/fusion-count deltas and
  (with ``--mesh``) the per-axis collective-inventory byte delta with
  replica-group changes named.

Provenance (ISSUE 14 stamping): entries whose stamped *configuration*
differs (jax/jaxlib, XLA_FLAGS, mesh, dtype, chain_steps, batch — git SHA
is exempt: differing code is the point) are REFUSED with the differing keys
named; ``--force`` overrides. Unstamped (pre-ISSUE-14) artifacts compare
with a note.

``--events E`` appends a ``run_compare`` JSONL record.
``--self-test`` is the verify.sh gate: identical twins must diff clean (no
category/bucket over the noise floor), and three injected known-cause
slowdowns — a 3x synthetic conv slowdown, the loader-sleep seam, the
async-committer delay seam — must each be attributed to the correct
category/bucket with evidence refs.

Exit codes: 0 report produced / self-test passed, 1 self-test failure,
2 provenance refusal (re-run with --force), 3 unusable input.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from distributed_training_pytorch_tpu.profiling import diff as diff_lib  # noqa: E402
from distributed_training_pytorch_tpu.telemetry import history as history_lib  # noqa: E402
from distributed_training_pytorch_tpu.telemetry import provenance as prov_lib  # noqa: E402

DEFAULT_NOISE_FLOOR = 0.10


# ---------------------------------------------------------------------------
# Input detection + loading
# ---------------------------------------------------------------------------


def detect_kind(path: str) -> str:
    """profile | run | bench — by what the path actually holds."""
    if path.endswith(".xplane.pb"):
        return "profile"
    if os.path.isdir(path):
        if os.path.exists(os.path.join(path, "telemetry", "events.jsonl")):
            return "run"
        from distributed_training_pytorch_tpu.profiling import latest_trace_file

        if latest_trace_file(path) is not None:
            return "profile"
        raise ValueError(
            f"{path}: directory holds neither telemetry/events.jsonl (a run "
            "dir) nor a *.xplane.pb trace (a profile capture)"
        )
    if os.path.basename(path) == "events.jsonl":
        return "run"
    if path.endswith((".json", ".jsonl")):
        return "bench"
    raise ValueError(
        f"{path}: cannot detect artifact kind (expected a *.xplane.pb trace, "
        "a run dir, or a bench *.json) — pass --kind explicitly"
    )


def load_bench_entry(path: str) -> dict:
    """One bench measurement dict from a committed round file (first entry,
    noting sweeps), a raw bench JSON line, or a JSONL file of lines."""
    if history_lib._ROUND_RE.search(os.path.basename(path)):
        entries = history_lib.load_round_file(path)
        if not entries:
            raise ValueError(f"{path}: round file carries no bench entries")
        if len(entries) > 1:
            print(f"run_compare: {path} is a {len(entries)}-entry sweep — "
                  "comparing its FIRST entry", file=sys.stderr)
        return entries[0].fields
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if isinstance(rec, dict) and ("metric" in rec or "step_ms" in rec):
                return rec
    raise ValueError(f"{path}: no bench JSON line found")


def load_run_summary(path: str) -> dict:
    """Distill a run dir's event log: cumulative goodput seconds (last
    snapshot), total steps, provenance (run_start), the last profile
    capture's categories, and the event-log lines the figures came from."""
    from distributed_training_pytorch_tpu.telemetry import timeline as timeline_lib

    run_dir = os.path.dirname(os.path.dirname(path)) if path.endswith(
        "events.jsonl") else path
    events = timeline_lib.load_run_events(run_dir)
    out = {
        "run_dir": os.path.abspath(run_dir),
        "goodput_seconds": None,
        "goodput_line": None,
        "steps": None,
        "provenance": None,
        "profile": None,
        "profile_line": None,
    }
    max_step = 0
    for rec in events:
        if rec.get("step") is not None:
            max_step = max(max_step, int(rec["step"]))
        if isinstance(rec.get("goodput_seconds"), dict):
            out["goodput_seconds"] = dict(rec["goodput_seconds"])
            out["goodput_line"] = rec.get("_line")
            # Pair the snapshot with the step count AT snapshot time (the
            # record's own counter, else the newest step seen so far) —
            # normalizing a mid-run snapshot by a LATER step counter (a
            # preempted run's windows past the last epoch_end) would
            # under-report every bucket's per-step wall.
            out["steps"] = (int(rec["step"]) if rec.get("step") is not None
                            else max_step)
        if rec.get("event") == "run_start" and isinstance(
            rec.get("provenance"), dict
        ):
            out["provenance"] = rec["provenance"]
        if rec.get("event") == "profile_capture" and isinstance(
            rec.get("categories"), dict
        ):
            out["profile"] = {
                "categories": rec["categories"],
                "step_us": rec.get("step_us"),
            }
            out["profile_line"] = rec.get("_line")
    if out["goodput_seconds"] is None:
        raise ValueError(
            f"{run_dir}: event log carries no goodput_seconds snapshot — "
            "was the run telemetry-on?"
        )
    if not out["steps"]:
        raise ValueError(
            f"{run_dir}: no goodput snapshot covering completed steps — "
            "nothing to normalize per-step"
        )
    return out


# ---------------------------------------------------------------------------
# The three comparisons (all through profiling.diff — the ONE attribution)
# ---------------------------------------------------------------------------


def check_provenance(before: "dict | None", after: "dict | None",
                     force: bool) -> "tuple[bool, list[str], str]":
    """(ok, differing_keys, note). Refusal is the ok=False case."""
    if not before or not after:
        return True, [], ("one or both sides carry no provenance stamp "
                          "(pre-ISSUE-14 artifact) — comparing unverified")
    keys = prov_lib.differing_keys(before, after)
    if not keys:
        sha = (before.get("git_sha"), after.get("git_sha"))
        return True, [], f"provenance OK (git {sha[0]} -> {sha[1]})"
    if force:
        return True, keys, (
            f"provenance DIFFERS on {', '.join(keys)} — compared anyway (--force)"
        )
    return False, keys, (
        f"provenance DIFFERS on {', '.join(keys)} — these entries measure "
        "different programs; re-run with --force to compare anyway"
    )


def compare_profiles(path_a: str, path_b: str, *, ridge=None, top=6,
                     noise_floor=DEFAULT_NOISE_FLOOR) -> dict:
    from distributed_training_pytorch_tpu.profiling import analyze_trace

    diff = diff_lib.diff_profiles(
        analyze_trace(path_a), analyze_trace(path_b), ridge_intensity=ridge,
    )
    clean = diff.max_category_delta_frac() <= noise_floor
    return {
        "kind": "profile",
        "clean": clean,
        "step_delta_ms": diff.step_delta_us / 1e3,
        "top_rows": [r.to_dict() for r in diff.categories[:top]],
        "new_ops": [o.name for o in diff.new_ops],
        "removed_ops": [o.name for o in diff.removed_ops],
        "roofline_shifts": [o.to_dict() for o in diff.roofline_shifts],
        "report": (
            ("CLEAN — no category exceeds the "
             f"{100 * noise_floor:.0f}% noise floor\n" if clean else "")
            + diff.describe(top=top)
        ),
        "provenance": (None, None),
    }


def steady_diff(seconds_a: dict, seconds_b: dict, *,
                noise_floor=DEFAULT_NOISE_FLOOR) -> dict:
    """Steady-state goodput-fraction diff between two bucket-seconds dicts —
    THE clean check of run-vs-run comparison, shared verbatim with the
    fleet controller's knob A/B (ISSUE 16: a tune is kept only when this
    diff says the targeted fraction actually moved, judged by the same
    code an operator's ``run_compare.py`` would run).

    Fractions are the doctor's steady-state ones (compile/restart/
    overlapped-commit excluded from the denominator), diffed through the
    ONE delta-attribution implementation (``profiling.diff``). Returns
    ``{"rows": [...], "max_delta": float, "clean": bool, "fractions":
    (a, b)}`` — rows ranked by |delta|, ``clean`` = nothing moved past the
    noise floor."""
    from distributed_training_pytorch_tpu.telemetry import doctor as doctor_lib

    steady_a = doctor_lib.steady_fractions(dict(seconds_a))
    steady_b = doctor_lib.steady_fractions(dict(seconds_b))
    rows = diff_lib.attribute_delta(steady_a, steady_b)
    max_delta = max((abs(r.delta) for r in rows), default=0.0)
    return {
        "rows": rows,
        "max_delta": max_delta,
        "clean": max_delta <= noise_floor,
        "fractions": (steady_a, steady_b),
    }


def compare_runs(path_a: str, path_b: str, *, top=6,
                 noise_floor=DEFAULT_NOISE_FLOOR) -> dict:
    a = load_run_summary(path_a)
    b = load_run_summary(path_b)
    # Per-step wall per goodput bucket (ms): the bucket seconds the doctor
    # reads, normalized by each run's own step count so runs of different
    # lengths compare. Deltas sum to the per-step total-wall delta exactly
    # (the one attribute_delta rule).
    per_step_a = {k: v / a["steps"] * 1e3 for k, v in a["goodput_seconds"].items()}
    per_step_b = {k: v / b["steps"] * 1e3 for k, v in b["goodput_seconds"].items()}
    rows = diff_lib.attribute_delta(per_step_a, per_step_b)
    # The clean check runs on STEADY-STATE fractions (compile/restart/
    # overlapped-commit excluded — the doctor's denominator), so a twin
    # pair differing only in XLA warmup wall still reads clean.
    sd = steady_diff(a["goodput_seconds"], b["goodput_seconds"],
                     noise_floor=noise_floor)
    steady_rows = sd["rows"]
    max_steady_delta = sd["max_delta"]
    clean = sd["clean"]

    total_delta = sum(r.delta for r in rows)
    lines = []
    if clean:
        lines.append(
            f"CLEAN — no steady-state bucket fraction moved more than the "
            f"{100 * noise_floor:.0f}% noise floor "
            f"(max |delta| {100 * max_steady_delta:.1f}%)"
        )
    lines.append(
        f"per-step wall {sum(per_step_a.values()):.2f} -> "
        f"{sum(per_step_b.values()):.2f} ms ({total_delta:+.2f} ms): "
        + diff_lib.describe_rows(rows, top=top)
    )
    lines.append(
        f"  evidence: goodput snapshots {a['run_dir']}/telemetry/"
        f"events.jsonl:{a['goodput_line']} vs {b['run_dir']}/telemetry/"
        f"events.jsonl:{b['goodput_line']} "
        f"({a['steps']} vs {b['steps']} steps)"
    )
    profile_rows = None
    if a["profile"] and b["profile"]:
        profile_rows = diff_lib.attribute_entry_delta(
            {"step_ms": (a["profile"]["step_us"] or 0) / 1e3,
             "categories": a["profile"]["categories"]},
            {"step_ms": (b["profile"]["step_us"] or 0) / 1e3,
             "categories": b["profile"]["categories"]},
        )
        if profile_rows:
            lines.append(
                "profile categories: " + diff_lib.describe_rows(profile_rows, top=top)
            )
            lines.append(
                f"  evidence: profile_capture events at lines "
                f"{a['profile_line']} vs {b['profile_line']}"
            )
    return {
        "kind": "run",
        "clean": clean,
        "step_delta_ms": total_delta,
        "top_rows": [r.to_dict() for r in rows[:top]],
        "steady_rows": [r.to_dict() for r in steady_rows[:top]],
        "profile_rows": [r.to_dict() for r in profile_rows[:top]] if profile_rows else None,
        "report": "\n".join(lines),
        "provenance": (a["provenance"], b["provenance"]),
    }


def compare_bench(path_a: str, path_b: str, *, top=6,
                  noise_floor=DEFAULT_NOISE_FLOOR) -> dict:
    a = load_bench_entry(path_a)
    b = load_bench_entry(path_b)
    lines = []
    headline = []
    for field in ("step_ms", "value", "mfu", "mfu_exec", "mfu_xla",
                  "comm_bytes_per_step"):
        va, vb = a.get(field), b.get(field)
        if isinstance(va, (int, float)) and isinstance(vb, (int, float)):
            change = (vb / va - 1.0) if va else 0.0
            headline.append({"field": field, "before": va, "after": vb,
                             "change": change})
            lines.append(
                f"{field}: {va:.4g} -> {vb:.4g} ({100 * change:+.2f}%)"
            )
    if not headline:
        raise ValueError("the two bench entries share no comparable numeric field")
    step_fields = {h["field"]: h for h in headline}
    # Clean = EVERY shared headline figure within the floor — two entries
    # sharing only mfu_exec/comm_bytes must not read clean while one of
    # those halved (headline is non-empty here, so this is never vacuous).
    clean = all(abs(h["change"]) <= noise_floor for h in headline)
    rows = diff_lib.attribute_entry_delta(a, b)
    if rows:
        lines.append(
            "step_ms attribution (BENCH_PROFILE categories): "
            + diff_lib.describe_rows(rows, top=top)
        )
    elif "step_ms" in step_fields:
        lines.append(
            "  (no category attribution: one or both entries lack "
            "BENCH_PROFILE=1 `categories` — re-run the sweep with it to get "
            "pre-diagnosed deltas)"
        )
    return {
        "kind": "bench",
        "clean": clean,
        "step_delta_ms": (
            step_fields["step_ms"]["after"] - step_fields["step_ms"]["before"]
            if "step_ms" in step_fields else 0.0
        ),
        "headline": headline,
        "top_rows": [r.to_dict() for r in rows[:top]] if rows else None,
        "report": "\n".join(lines),
        "provenance": (a.get("provenance"), b.get("provenance")),
    }


def compare_hlo(path_a: str, path_b: str, *, mesh_spec=None, top=6) -> dict:
    from distributed_training_pytorch_tpu.analysis import diff as adiff

    with open(path_a, encoding="utf-8") as f:
        text_a = f.read()
    with open(path_b, encoding="utf-8") as f:
        text_b = f.read()
    struct = adiff.diff_hlo(text_a, text_b, label_before=path_a, label_after=path_b)
    lines = [struct.describe(top=top)]
    comm = None
    if mesh_spec:
        from distributed_training_pytorch_tpu import compat
        from distributed_training_pytorch_tpu.analysis import collective_inventory
        from distributed_training_pytorch_tpu.parallel.mesh import (
            mesh_config_from_spec,
        )

        cfg = mesh_config_from_spec(mesh_spec)
        # The comm diff is pure text analysis, but axis mapping needs a
        # device mesh of the spec's extent — force virtual host devices
        # (the PR 11 helper every comm-audit consumer uses) so `--mesh
        # fsdp8` works on a 1-device laptop. Safe here: nothing before the
        # hlo path initializes the backend.
        compat.force_host_devices(
            max(cfg.data, 1) * cfg.fsdp * cfg.pipe * cfg.expert * cfg.seq
            * cfg.tensor
        )
        mesh = cfg.build()
        comm = adiff.diff_comm(
            collective_inventory(text_a, mesh, label=path_a),
            collective_inventory(text_b, mesh, label=path_b),
        )
        lines.append(comm.describe(top=top))
    return {
        "kind": "hlo",
        "clean": struct.identical and (comm is None or comm.identical),
        "step_delta_ms": 0.0,
        "structural": struct.to_dict(),
        "comm": comm.to_dict() if comm else None,
        "report": "\n".join(lines),
        "provenance": (None, None),
    }


# ---------------------------------------------------------------------------
# Self-test (the verify.sh stage)
# ---------------------------------------------------------------------------


def _synthetic_trace(tmp: str, name: str, conv_us: float) -> str:
    """A one-plane device trace: conv + fusion + a dispatch gap, conv
    duration parameterized — the injected-3x seam of the self-test."""
    from distributed_training_pytorch_tpu.profiling import xplane

    us = 1_000_000  # ps per us
    events = [
        ("%convolution.1", 0, int(conv_us * us)),
        ("%fusion.2", int(conv_us * us), 200 * us),
        # 100 us dispatch gap, then the tail op.
        ("%copy.3", int(conv_us * us) + 300 * us, 100 * us),
    ]
    path = os.path.join(tmp, f"{name}.xplane.pb")
    with open(path, "wb") as f:  # jaxlint: disable=file-write-without-rank-gate -- offline self-test fixture synthesis, single process by contract
        f.write(xplane.encode_xspace([{
            "name": "/device:TPU:0",
            "lines": [{"name": "XLA Ops", "timestamp_ns": 0, "events": events}],
        }]))
    return path


def self_test() -> int:
    import shutil
    import tempfile

    failures: list[str] = []

    # [1] Identical synthetic twins must diff clean; a 3x-slower conv must
    # be attributed to `convolution` with the delta fraction dominating.
    tmp = tempfile.mkdtemp(prefix="run_compare_selftest_")
    try:
        twin_a = _synthetic_trace(tmp, "twin_a", conv_us=500)
        twin_b = _synthetic_trace(tmp, "twin_b", conv_us=500)
        slow = _synthetic_trace(tmp, "slow", conv_us=1500)
        res = compare_profiles(twin_a, twin_b)
        print(f"run_compare self-test [twin-profiles]: "
              f"{'clean' if res['clean'] else 'NOT CLEAN'}")
        if not res["clean"]:
            failures.append(f"identical twin traces did not diff clean: {res['report']}")
        res = compare_profiles(twin_a, slow)
        top = res["top_rows"][0]
        print(f"run_compare self-test [3x-conv]: top category "
              f"{top['key']!r} ({top['delta']:+.0f} us, "
              f"{100 * top['frac_of_delta']:.0f}% of delta)")
        if res["clean"] or top["key"] != "convolution" or top["frac_of_delta"] < 0.9:
            failures.append(
                f"injected 3x conv slowdown misattributed: {res['report']}"
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # [2] Real-trainer legs, through the SAME injection seams the perf gate
    # and doctor self-tests use (run_doctor._self_test_trainer): identical
    # twins clean, loader sleep -> data_wait, committer delay -> the
    # checkpoint/checkpoint_async backpressure buckets.
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run_doctor

    dirs: dict[str, str] = {}
    legs = [
        ("clean_a", {}),
        ("clean_b", {}),
        ("loader-sleep", {"load_delay_s": 0.05}),
        ("commit-delay", {"commit_delay_s": 0.6}),
    ]
    try:
        from distributed_training_pytorch_tpu.telemetry import Telemetry

        for name, kw in legs:
            d = tempfile.mkdtemp(prefix=f"run_compare_{name}_")
            dirs[name] = d
            trainer = run_doctor._self_test_trainer(
                d, telemetry=Telemetry(anomaly=None, mfu=False), **kw
            )
            trainer.train()
        res = compare_runs(dirs["clean_a"], dirs["clean_b"])
        print(f"run_compare self-test [twin-runs]: "
              f"{'clean' if res['clean'] else 'NOT CLEAN'}")
        print("  " + res["report"].replace("\n", "\n  "))
        if not res["clean"]:
            failures.append(
                f"identical twin runs did not diff clean: {res['report']}"
            )
        # The provenance stamp must have ridden run_start (ISSUE 14
        # satellite) and the twins' configurations must compare equal.
        prov_a, prov_b = res["provenance"]
        if not prov_a or not prov_b:
            failures.append("run_start carried no provenance stamp")
        elif prov_lib.differing_keys(prov_a, prov_b):
            failures.append(
                "twin runs' provenance configurations differ: "
                f"{prov_lib.differing_keys(prov_a, prov_b)}"
            )
        for name, want in (
            ("loader-sleep", ("data_wait",)),
            ("commit-delay", ("checkpoint", "checkpoint_async")),
        ):
            res = compare_runs(dirs["clean_a"], dirs[name])
            top = res["top_rows"][0]
            print(f"run_compare self-test [{name}]: top bucket {top['key']!r} "
                  f"({top['delta']:+.2f} ms/step)")
            if res["clean"] or top["key"] not in want or top["delta"] <= 0:
                failures.append(
                    f"injected {name} misattributed (wanted {want}, got "
                    f"{top['key']!r}): {res['report']}"
                )
    finally:
        for d in dirs.values():
            shutil.rmtree(d, ignore_errors=True)

    if failures:
        print("RUN COMPARE SELF-TEST FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print("run_compare self-test OK: twins diff clean; 3x-conv, loader-sleep "
          "and commit-delay each attributed to the correct category/bucket")
    return 0


# ---------------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("before", nargs="?", help="the A side (baseline)")
    parser.add_argument("after", nargs="?", help="the B side (candidate)")
    parser.add_argument("--kind", default="auto",
                        choices=("auto", "bench", "profile", "run", "hlo"),
                        help="artifact kind (default: auto-detect per side)")
    parser.add_argument("--force", action="store_true",
                        help="compare despite differing provenance configuration")
    parser.add_argument("--mesh", default=None,
                        help="mesh spec (e.g. fsdp4x2) for --kind hlo comm diffing")
    parser.add_argument("--ridge", type=float, default=None,
                        help="roofline ridge intensity (FLOPs/byte) to classify "
                             "memory<->compute bound shifts")
    parser.add_argument("--top", type=int, default=6,
                        help="rows per attribution section (default %(default)s)")
    parser.add_argument("--noise-floor", type=float, default=DEFAULT_NOISE_FLOOR,
                        help="clean-verdict floor: max category/bucket move, as "
                             "a fraction (default %(default)s)")
    parser.add_argument("--json", action="store_true",
                        help="print the comparison as one JSON object")
    parser.add_argument("--events", default=None,
                        help="append a run_compare record to this JSONL event log")
    parser.add_argument("--self-test", action="store_true",
                        help="CI gate: twins clean + injected slowdowns "
                             "attributed (verify.sh)")
    args = parser.parse_args()
    if args.self_test:
        return self_test()
    if not args.before or not args.after:
        parser.error("BEFORE and AFTER are required (or use --self-test)")

    try:
        if args.kind == "auto":
            kind_a, kind_b = detect_kind(args.before), detect_kind(args.after)
            if kind_a != kind_b:
                print(f"run_compare: {args.before} is a {kind_a} but "
                      f"{args.after} is a {kind_b} — same kind required",
                      file=sys.stderr)
                return 3
            kind = kind_a
        else:
            kind = args.kind
        if kind == "profile":
            result = compare_profiles(args.before, args.after, ridge=args.ridge,
                                      top=args.top, noise_floor=args.noise_floor)
        elif kind == "run":
            result = compare_runs(args.before, args.after, top=args.top,
                                  noise_floor=args.noise_floor)
        elif kind == "bench":
            result = compare_bench(args.before, args.after, top=args.top,
                                   noise_floor=args.noise_floor)
        else:
            result = compare_hlo(args.before, args.after, mesh_spec=args.mesh,
                                 top=args.top)
    except (FileNotFoundError, ValueError) as e:
        print(f"run_compare: {e}", file=sys.stderr)
        return 3

    ok, keys, note = check_provenance(*result["provenance"], args.force)
    print(f"run_compare [{result['kind']}]: {args.before} -> {args.after}")
    print(f"  {note}")
    if not ok:
        return 2
    if args.json:
        out = {k: v for k, v in result.items() if k not in ("report", "provenance")}
        out["provenance_differs"] = keys
        print(json.dumps(out, indent=2, sort_keys=True))
    else:
        print(result["report"])

    if args.events:
        from distributed_training_pytorch_tpu.telemetry import EventLog

        EventLog(args.events, process_index=0).emit(
            "run_compare",
            kind=result["kind"],
            before=str(args.before),
            after=str(args.after),
            clean=result["clean"],
            step_delta_ms=result["step_delta_ms"],
            top_rows=result.get("top_rows"),
            provenance_differs=keys,
            forced=bool(keys and args.force),
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
