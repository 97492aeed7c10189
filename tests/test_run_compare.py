"""Run-comparison layer tests (ISSUE 14; docs/profiling.md §before/after).

Four layers, mirroring the subsystem:

* ``profiling.diff`` — the ONE delta-attribution rule (exact hand-computed
  deltas, fractions of delta summing to 1 by construction) and
  ``diff_profiles`` on synthetic ``encode_xspace`` trace pairs (exact
  category deltas, new/removed op detection, roofline shifts);
* ``analysis.diff`` — HLO op-category/fusion-count deltas and the comm
  inventory delta on hand-built programs (per-axis byte deltas, replica
  group changes named);
* ``telemetry.history`` + ``telemetry.provenance`` — flat-streak detector
  boundary cases (N-1 rounds flat = quiet, N = fires), regression
  direction, round-file ingestion, provenance compare semantics — plus the
  committed-BENCH self-parity: the r02→r05 plateau MUST be detected on the
  repo's own committed files;
* the CLIs — scripts/run_compare.py + scripts/perf_gate.py share ONE diff
  implementation (AST-enforced: neither defines a private attribution),
  and run_compare compares two committed bench rounds end to end.
"""

import ast
import json
import math
import os
import subprocess
import sys

import pytest

from distributed_training_pytorch_tpu.analysis import diff as analysis_diff
from distributed_training_pytorch_tpu.analysis.comm_audit import collective_inventory
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.profiling import IDLE, analyze_trace, xplane
from distributed_training_pytorch_tpu.profiling import diff as diff_lib
from distributed_training_pytorch_tpu.telemetry import history as history_lib
from distributed_training_pytorch_tpu.telemetry import provenance as prov_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1_000_000  # picoseconds per microsecond


# ---------------------------------------------------------------------------
# attribute_delta: the one rule
# ---------------------------------------------------------------------------


class TestAttributeDelta:
    def test_exact_deltas_and_fraction_sum(self):
        rows = diff_lib.attribute_delta(
            {"conv": 40.0, "idle": 10.0}, {"conv": 120.0, "idle": 10.0}
        )
        assert [r.key for r in rows] == ["conv", "idle"]
        assert rows[0].delta == 80.0 and rows[1].delta == 0.0
        assert math.isclose(sum(r.frac_of_delta for r in rows), 1.0)

    def test_union_of_keys_absent_is_zero(self):
        rows = diff_lib.attribute_delta({"a": 5.0}, {"b": 3.0})
        by_key = {r.key: r for r in rows}
        assert by_key["a"].delta == -5.0 and by_key["a"].after == 0.0
        assert by_key["b"].delta == 3.0 and by_key["b"].before == 0.0
        # deltas sum to the total delta exactly; signed fractions sum to 1
        assert math.isclose(sum(r.delta for r in rows), -2.0)
        assert math.isclose(sum(r.frac_of_delta for r in rows), 1.0)

    def test_ranked_by_abs_delta(self):
        rows = diff_lib.attribute_delta(
            {"a": 1.0, "b": 1.0, "c": 1.0}, {"a": 2.0, "b": 10.0, "c": 0.5}
        )
        assert [r.key for r in rows] == ["b", "a", "c"]

    def test_identical_totals_zero_fractions(self):
        rows = diff_lib.attribute_delta({"a": 2.0, "b": 1.0}, {"a": 1.0, "b": 2.0})
        # total delta is 0: per-key deltas exist, fractions refuse to divide
        assert all(r.frac_of_delta == 0.0 for r in rows)
        assert {r.key: r.delta for r in rows} == {"a": -1.0, "b": 1.0}

    def test_entry_delta_exact_and_degrades(self):
        before = {"step_ms": 10.0, "categories": {"conv": 0.8, "idle": 0.2}}
        after = {"step_ms": 14.0, "categories": {"conv": 0.9, "idle": 0.1}}
        rows = diff_lib.attribute_entry_delta(before, after)
        by_key = {r.key: r for r in rows}
        assert math.isclose(by_key["conv"].delta, 12.6 - 8.0)
        assert math.isclose(by_key["idle"].delta, 1.4 - 2.0)
        assert math.isclose(sum(r.delta for r in rows), 4.0)
        assert diff_lib.attribute_entry_delta({"step_ms": 10.0}, after) is None
        assert diff_lib.attribute_entry_delta(
            {"step_ms": 10.0, "categories": {}}, after) is None


# ---------------------------------------------------------------------------
# diff_profiles on synthetic encode_xspace pairs (hand-computed)
# ---------------------------------------------------------------------------


def _write_trace(tmp_path, name: str, conv_us: int) -> str:
    """One device plane, sequential critical path: conv (parameterized) +
    fusion 20 + a 5us gap + copy 10 + all-reduce 15 + dot 5, then a 5us
    trailing gap closed by a 0-width marker? No — the span ends at the last
    event, so idle is exactly the one 5us gap + nothing else. Events are
    laid out so category self-times are round numbers and idle is 10us:
    two 5us gaps (after fusion, after all-reduce)."""
    c = conv_us
    events = [
        (f"%convolution.1 = f32[8,16,16,8] convolution(%p0, %p1)", 0 * US, c * US),
        ("%fusion.7 = f32[8,16,16,8] fusion(%param.4)", c * US, 20 * US),
        ("%copy.3 = f32[8,8,16,16] copy(%fusion.7)", (c + 25) * US, 10 * US),
        ("%all-reduce.2 = f32[10] all-reduce(%copy.3)", (c + 35) * US, 15 * US),
        ("%dot.5 = f32[8,10] dot(%fusion.7, %p2)", (c + 55) * US, 5 * US),
    ]
    path = str(tmp_path / f"{name}.xplane.pb")
    with open(path, "wb") as f:
        f.write(xplane.encode_xspace([{
            "name": "/device:TPU:0",
            "lines": [{"name": "XLA Ops", "timestamp_ns": 0, "events": events}],
        }]))
    return path


class TestDiffProfiles:
    def test_hand_computed_category_deltas(self, tmp_path):
        # before: conv 40 -> span 100 (busy 90, idle 10);
        # after:  conv 120 -> span 180 (busy 170, idle 10).
        # Per-category per-step us both sides are the raw self-times + idle,
        # so the ONLY delta is convolution +80us — 100% of the step delta.
        before = analyze_trace(_write_trace(tmp_path, "before", 40))
        after = analyze_trace(_write_trace(tmp_path, "after", 120))
        diff = diff_lib.diff_profiles(before, after)
        assert math.isclose(diff.step_delta_us, 80.0, abs_tol=1e-6)
        top = diff.categories[0]
        assert top.key == "convolution"
        assert math.isclose(top.delta, 80.0, abs_tol=1e-6)
        assert math.isclose(top.frac_of_delta, 1.0, abs_tol=1e-9)
        for row in diff.categories[1:]:
            assert abs(row.delta) < 1e-6, row
        # the exhaustive-partition invariant, across runs
        assert math.isclose(sum(r.frac_of_delta for r in diff.categories), 1.0)
        assert math.isclose(
            sum(r.delta for r in diff.categories), diff.step_delta_us, abs_tol=1e-6
        )
        assert {r.key for r in diff.categories} >= {IDLE, "convolution", "matmul"}
        # op join: the conv op carries the same +80us; everything matched
        assert diff.ops[0].name.startswith("%convolution.1")
        assert math.isclose(diff.ops[0].delta_us, 80.0, abs_tol=1e-6)
        assert not diff.new_ops and not diff.removed_ops
        assert diff.describe()  # renders

    def test_identical_twins_diff_clean(self, tmp_path):
        a = analyze_trace(_write_trace(tmp_path, "a", 40))
        b = analyze_trace(_write_trace(tmp_path, "b", 40))
        diff = diff_lib.diff_profiles(a, b)
        assert diff.max_category_delta_frac() == 0.0
        assert all(r.delta == 0 for r in diff.categories)

    def test_new_and_removed_ops_called_out(self):
        def report(ops):
            return {
                "trace_path": "t", "source": "device", "steps": 1,
                "span_us": 100.0, "busy_us": 100.0, "idle_us": 0.0,
                "step_us": 100.0, "categories": {"convolution": 1.0},
                "category_us": {}, "top_ops": ops,
            }

        before = report([
            {"name": "%convolution.1", "category": "convolution",
             "total_us": 60.0, "count": 1, "frac_busy": 0.6},
            {"name": "%dot.2", "category": "matmul",
             "total_us": 40.0, "count": 1, "frac_busy": 0.4},
        ])
        after = report([
            {"name": "%convolution.1", "category": "convolution",
             "total_us": 60.0, "count": 1, "frac_busy": 0.6},
            {"name": "%pallas_call.9", "category": "matmul",
             "total_us": 20.0, "count": 1, "frac_busy": 0.4},
        ])
        diff = diff_lib.diff_profiles(before, after)
        assert [o.name for o in diff.new_ops] == ["%pallas_call.9"]
        assert [o.name for o in diff.removed_ops] == ["%dot.2"]
        removed = {o.name: o for o in diff.ops}["%dot.2"]
        assert removed.after_us == 0.0 and removed.delta_us == -40.0

    def test_roofline_shift_classified_against_ridge(self):
        def report(intensity):
            return {
                "trace_path": "t", "source": "device", "steps": 1,
                "span_us": 100.0, "busy_us": 100.0, "idle_us": 0.0,
                "step_us": 100.0, "categories": {"convolution": 1.0},
                "category_us": {}, "top_ops": [
                    {"name": "%convolution.1", "category": "convolution",
                     "total_us": 100.0, "count": 1, "frac_busy": 1.0,
                     "arith_intensity": intensity},
                ],
            }

        # 80 F/B -> 250 F/B across a 200 F/B ridge: the Pallas-win signature
        diff = diff_lib.diff_profiles(report(80), report(250), ridge_intensity=200)
        assert [o.bound_shift for o in diff.roofline_shifts] == ["memory->compute"]
        # no ridge given -> intensities carried, shift not classified
        diff = diff_lib.diff_profiles(report(80), report(250))
        assert not diff.roofline_shifts
        assert diff.ops[0].intensity_before == 80
        # same side of the ridge -> no shift
        diff = diff_lib.diff_profiles(report(80), report(150), ridge_intensity=200)
        assert not diff.roofline_shifts

    def test_per_step_normalization_uses_step_us(self):
        def report(step_us, steps):
            return {
                "trace_path": "t", "source": "device", "steps": steps,
                "span_us": step_us * steps, "busy_us": step_us * steps,
                "idle_us": 0.0, "step_us": step_us,
                "categories": {"matmul": 1.0}, "category_us": {}, "top_ops": [],
            }

        # 4-step trace vs 2-step trace with the SAME per-step time: clean.
        diff = diff_lib.diff_profiles(report(50.0, 4), report(50.0, 2))
        assert diff.step_delta_us == 0.0


# ---------------------------------------------------------------------------
# analysis.diff: HLO structural + comm deltas on hand-built programs
# ---------------------------------------------------------------------------


HLO_BEFORE = """\
HloModule step
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %fusion.1 = f32[8,8]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation
  %convolution.2 = f32[8,8]{1,0} convolution(%fusion.1, %p0), window={size=3x3}
  %dot.3 = f32[8,8]{1,0} dot(%convolution.2, %p0), lhs_contracting_dims={1}
  ROOT %copy.4 = f32[8,8]{1,0} copy(%dot.3)
}
"""

# The "Pallas landed" twin: the conv became a custom-call, one fusion split
# into two, and a collective appeared.
HLO_AFTER = """\
HloModule step
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %fusion.1 = f32[8,8]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation
  %fusion.5 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fc2
  %custom-call.2 = f32[8,8]{1,0} custom-call(%fusion.5, %p0), custom_call_target="pallas_conv"
  %dot.3 = f32[8,8]{1,0} dot(%custom-call.2, %p0), lhs_contracting_dims={1}
  %all-reduce.6 = f32[8,8]{1,0} all-reduce(%dot.3), replica_groups=[1,8]<=[8], to_apply=%add
  ROOT %copy.4 = f32[8,8]{1,0} copy(%all-reduce.6)
}
"""


class TestHloStructuralDiff:
    def test_signature_hand_counts(self):
        sig = analysis_diff.hlo_signature(HLO_BEFORE)
        assert sig.instructions == 5
        assert sig.fusions == 1
        assert sig.collectives == 0
        assert sig.category_counts == {
            "other": 1,  # parameter
            "fusion(elementwise)": 1,
            "convolution": 1,
            "matmul": 1,
            "copy/transpose": 1,
        }
        assert sig.opcode_counts["parameter"] == 1

    def test_tuple_typed_instruction_parses(self):
        text = "  %t = (f32[2]{0}, s32[]) tuple(%a, %b)\n"
        assert list(analysis_diff.iter_instruction_opcodes(text)) == [("%t", "tuple")]

    def test_diff_hand_computed(self):
        diff = analysis_diff.diff_hlo(HLO_BEFORE, HLO_AFTER)
        assert diff.instruction_delta == 2
        assert diff.fusion_delta == 1
        assert diff.collective_delta == 1
        deltas = {r.key: r.delta for r in diff.category_deltas}
        # conv -> custom-call: convolution bucket -1, matmul (custom-call) +1
        assert deltas["convolution"] == -1
        assert deltas["matmul"] == 1
        assert deltas["fusion(elementwise)"] == 1
        assert deltas["collective"] == 1
        assert not diff.identical
        assert "fusions 1 -> 2" in diff.describe()

    def test_identical_program(self):
        diff = analysis_diff.diff_hlo(HLO_BEFORE, HLO_BEFORE)
        assert diff.identical
        assert "identical" in diff.describe()


class TestCommDiff:
    @pytest.fixture()
    def mesh(self, devices):
        return mesh_lib.create_mesh(
            {mesh_lib.DATA_AXIS: 4, mesh_lib.TENSOR_AXIS: 2}, devices=devices
        )

    def test_per_axis_deltas_and_regroup_named(self, mesh):
        # before: one all-reduce over the tensor pairs (groups of 2);
        # after: the SAME instruction name regrouped over the data columns.
        before = collective_inventory(
            "  %all-reduce.3 = f32[10,512]{1,0} all-reduce(f32[10,512]{1,0} "
            "%dot.2), channel_id=8, replica_groups=[4,2]<=[8], "
            "use_global_device_ids=true, to_apply=%add\n",
            mesh,
        )
        after = collective_inventory(
            "  %all-reduce.3 = f32[10,512]{1,0} all-reduce(f32[10,512]{1,0} "
            "%dot.2), channel_id=8, replica_groups=[2,4]<=[4,2]T(1,0), "
            "use_global_device_ids=true, to_apply=%add\n",
            mesh,
        )
        bytes_ = 10 * 512 * 4
        assert before.collectives[0].axes == ("tensor",)
        assert after.collectives[0].axes == ("data",)
        diff = analysis_diff.diff_comm(before, after)
        deltas = {r.key: r.delta for r in diff.axis_deltas}
        assert deltas == {"tensor": -bytes_, "data": bytes_}
        assert diff.total_delta == 0
        assert len(diff.group_changes) == 1
        change = diff.group_changes[0]
        assert change.startswith("REGROUPED %all-reduce.3")
        assert "4 group(s) of 2 over tensor -> 2 group(s) of 4 over data" in change

    def test_new_and_removed_collectives_named(self, mesh):
        before = collective_inventory(
            "  %all-reduce.1 = f32[512]{0} all-reduce(f32[512]{0} %g), "
            "replica_groups=[2,4]<=[4,2]T(1,0), to_apply=%add\n",
            mesh,
        )
        after = collective_inventory(
            "  %all-gather.9 = f32[512,8]{1,0} all-gather(f32[512,4]{1,0} %w), "
            "replica_groups=[4,2]<=[8], dimensions={1}\n",
            mesh,
        )
        diff = analysis_diff.diff_comm(before, after)
        kinds = sorted(c.split()[0] for c in diff.group_changes)
        assert kinds == ["NEW", "REMOVED"]
        assert any("%all-gather.9" in c for c in diff.group_changes if "NEW" in c)
        op_deltas = {r.key: r.delta for r in diff.op_deltas}
        assert op_deltas["all-reduce"] == -(512 * 4)
        assert op_deltas["all-gather"] == 512 * 8 * 4

    def test_identical_inventories(self, mesh):
        text = ("  %all-reduce.1 = f32[512]{0} all-reduce(f32[512]{0} %g), "
                "replica_groups=[1,8]<=[8], to_apply=%add\n")
        diff = analysis_diff.diff_comm(
            collective_inventory(text, mesh), collective_inventory(text, mesh)
        )
        assert diff.identical
        assert "identical" in diff.describe()


# ---------------------------------------------------------------------------
# telemetry.history: detectors + round ingestion + committed self-parity
# ---------------------------------------------------------------------------


class TestFlatStreakDetector:
    def test_n_minus_one_quiet_n_fires(self):
        flat3 = [(1, 100.0), (2, 100.5), (3, 99.8)]
        assert history_lib.detect_flat_streaks(flat3, min_rounds=4) == []
        flat4 = flat3 + [(4, 100.2)]
        streaks = history_lib.detect_flat_streaks(flat4, min_rounds=4)
        assert len(streaks) == 1
        assert streaks[0].rounds == [1, 2, 3, 4]
        assert streaks[0].spread < 0.02

    def test_band_boundary(self):
        # spread 2.96% > 2% band: no streak even at min_rounds=2
        assert history_lib.detect_flat_streaks(
            [(1, 100.0), (2, 103.0)], min_rounds=2) == []
        # spread 1.49% fits
        assert len(history_lib.detect_flat_streaks(
            [(1, 100.0), (2, 101.5)], min_rounds=2)) == 1

    def test_maximal_windows_not_suffixes(self):
        # two plateaus split by a jump: exactly two maximal streaks, no
        # sub-window double-reports
        points = [(i, 100.0) for i in range(1, 4)] + [(i, 200.0) for i in range(4, 8)]
        streaks = history_lib.detect_flat_streaks(points, min_rounds=3)
        assert [s.rounds for s in streaks] == [[1, 2, 3], [4, 5, 6, 7]]

    def test_improving_series_is_not_flat(self):
        points = [(i, 100.0 * (1.10 ** i)) for i in range(1, 6)]
        assert history_lib.detect_flat_streaks(points, min_rounds=4) == []

    def test_min_rounds_validated(self):
        with pytest.raises(ValueError):
            history_lib.detect_flat_streaks([(1, 1.0)], min_rounds=1)


class TestRegressionDetector:
    def test_direction_aware(self):
        up = [(1, 100.0), (2, 110.0)]
        down = [(1, 100.0), (2, 90.0)]
        # step_ms up = bad
        assert len(history_lib.detect_regressions(up, "step_ms")) == 1
        assert history_lib.detect_regressions(down, "step_ms") == []
        # value down = bad
        assert len(history_lib.detect_regressions(down, "value")) == 1
        assert history_lib.detect_regressions(up, "value") == []
        # unknown direction: tracked, never accused
        assert history_lib.detect_regressions(up, "mystery_metric") == []

    def test_tolerance_boundary(self):
        assert history_lib.detect_regressions(
            [(1, 100.0), (2, 104.9)], "step_ms", rel_tol=0.05) == []
        found = history_lib.detect_regressions(
            [(1, 100.0), (2, 105.1)], "step_ms", rel_tol=0.05)
        assert len(found) == 1 and found[0].round_after == 2


class TestRoundIngestion:
    def test_tail_lines_preferred_and_parsed(self, tmp_path):
        path = str(tmp_path / "BENCH_r07.json")
        lines = [
            {"metric": "m", "value": 1.0, "dtype": "bf16", "step_ms": 10.0,
             "goodput": {"productive_step": 0.9, "compile": 0.1}},
            {"metric": "m", "value": 2.0, "dtype": "fp32", "step_ms": 20.0},
        ]
        with open(path, "w") as f:
            json.dump({
                "n": 7,
                "tail": "noise\n" + "\n".join(json.dumps(ln) for ln in lines),
                "parsed": {"metric": "m", "value": 1.0},
            }, f)
        entries = history_lib.load_round_file(path)
        assert len(entries) == 2  # both tail lines, parsed NOT duplicated
        assert entries[0].round == 7 and entries[0].kind == "bench"
        assert entries[0].series_label != entries[1].series_label  # dtype facet
        nums = entries[0].numeric_fields()
        assert nums["goodput.productive_step"] == 0.9
        assert "metric" not in nums

    def test_parsed_fallback(self, tmp_path):
        path = str(tmp_path / "MULTICHIP_r03.json")
        with open(path, "w") as f:
            json.dump({"tail": "no json here",
                       "parsed": {"metric": "m", "value": 3.0}}, f)
        entries = history_lib.load_round_file(path)
        assert len(entries) == 1 and entries[0].kind == "multichip"

    def test_non_round_file_rejected(self, tmp_path):
        path = str(tmp_path / "whatever.json")
        with open(path, "w") as f:
            f.write("{}")
        with pytest.raises(ValueError):
            history_lib.load_round_file(path)


def _write_round(root, n: int, value: float, step_ms: float) -> str:
    """One ``BENCH_rNN.json`` in the driver's round-file shape (``n``, the
    stdout ``tail`` holding the bench's JSON line, and ``parsed``)."""
    line = {"metric": "images/sec/chip (synthetic round)", "value": value,
            "unit": "images/sec/chip", "step_ms": step_ms, "dtype": "bf16"}
    path = os.path.join(str(root), f"BENCH_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump({"n": n, "tail": "noise\n" + json.dumps(line),
                   "parsed": line}, f)
    return path


@pytest.fixture
def rounds_dir(tmp_path):
    """Five synthetic rounds: r01 clearly lower, then a four-round plateau
    r02->r05 inside the flat band (spread ~1.4%)."""
    for n, value, step_ms in (
        (1, 800.0, 12.5), (2, 1000.0, 10.0), (3, 998.0, 10.02),
        (4, 1002.0, 9.98), (5, 988.0, 10.12),
    ):
        _write_round(tmp_path, n, value, step_ms)
    return tmp_path


def test_rounds_flat_streak_self_parity(rounds_dir):
    """The acceptance case on round files: an r02->r05 plateau (spread
    ~1.4%) must be detected on step_ms AND value."""
    report = history_lib.analyze_history(str(rounds_dir))
    assert report.entries, "no BENCH_r files found"
    for field in ("step_ms", "value"):
        hits = [s for s in report.streaks
                if s.series.endswith(f":: {field}")
                and s.rounds[0] <= 2 and s.rounds[-1] >= 5]
        assert hits, (field, [s.describe() for s in report.streaks])
        assert len(hits[0].rounds) >= 4
    # r01 (20% lower) must NOT be part of the value plateau
    value_hit = [s for s in report.streaks if s.series.endswith(":: value")][0]
    assert 1 not in value_hit.rounds


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------


class TestProvenance:
    def test_fields_present(self):
        prov = prov_lib.provenance_fields(
            mesh={"data": 8}, dtype="bf16", chain_steps=10, batch=4096
        )
        for key in ("git_sha", "jax", "jaxlib", "xla_flags", "mesh", "dtype",
                    "chain_steps", "batch"):
            assert key in prov
        assert prov["git_sha"]  # a sha in a checkout, "unknown" outside one
        assert prov["chain_steps"] == 10

    def test_differing_keys_names_config_not_sha(self):
        a = prov_lib.provenance_fields(dtype="bf16", chain_steps=10)
        b = dict(a, git_sha="deadbeef", dtype="fp32", chain_steps=1)
        keys = prov_lib.differing_keys(a, b)
        assert keys == ["dtype", "chain_steps"]
        assert "git_sha" not in keys

    def test_absent_sides_and_fields_compatible(self):
        a = prov_lib.provenance_fields(dtype="bf16")
        assert prov_lib.differing_keys(None, a) == []
        assert prov_lib.differing_keys(a, None) == []
        # a key absent/None on one side never disagrees (old entries)
        b = dict(a)
        b.pop("dtype")
        assert prov_lib.differing_keys(a, b) == []


# ---------------------------------------------------------------------------
# The CLIs: one shared diff implementation + end-to-end on round files
# ---------------------------------------------------------------------------


def _script_tree(name: str) -> ast.Module:
    with open(os.path.join(REPO, "scripts", name), encoding="utf-8") as f:
        return ast.parse(f.read(), filename=name)


@pytest.mark.parametrize("script", ["run_compare.py", "perf_gate.py"])
def test_scripts_share_the_one_diff_implementation(script):
    """Satellite 6 (test-enforced no drift): both CLIs import
    profiling.diff and define NO attribution/formatting of their own."""
    tree = _script_tree(script)
    imports_diff = any(
        isinstance(node, ast.ImportFrom)
        and node.module
        and node.module.endswith("profiling")
        and any(alias.name == "diff" for alias in node.names)
        for node in ast.walk(tree)
    )
    assert imports_diff, f"{script} must import profiling.diff (the ONE diff impl)"
    forbidden = ("attribute_delta", "attribute_entry_delta", "describe_rows",
                 "diff_profiles")
    local_defs = [
        node.name for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and (node.name in forbidden or "attribut" in node.name)
    ]
    assert not local_defs, (
        f"{script} defines a private attribution {local_defs} — the diff "
        "implementation lives in profiling/diff.py only"
    )


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_run_compare_cli_on_round_files(rounds_dir):
    """End to end on two round files: r02 vs r05 must produce a headline
    comparison (no provenance stamp on them — a note, not a refusal)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "run_compare.py"),
         str(rounds_dir / "BENCH_r02.json"), str(rounds_dir / "BENCH_r05.json")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "step_ms" in proc.stdout
    assert "provenance" in proc.stdout  # the unstamped-artifact note
    assert "value" in proc.stdout


@pytest.mark.slow  # soak-shaped: moved out of tier-1 to keep it inside its cap (PR 21)
def test_run_compare_provenance_refusal_and_force(tmp_path):
    """Two bench entries whose stamped configuration differs are refused
    (exit 2, keys named); --force compares them."""
    a = {"metric": "m", "value": 1.0, "step_ms": 10.0,
         "provenance": {"jax": "1", "dtype": "bf16"}}
    b = {"metric": "m", "value": 2.0, "step_ms": 12.0,
         "provenance": {"jax": "1", "dtype": "fp32"}}
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    for path, rec in ((pa, a), (pb, b)):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cmd = [sys.executable, os.path.join(REPO, "scripts", "run_compare.py"), pa, pb]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=180, env=env)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "dtype" in proc.stdout
    proc = subprocess.run(cmd + ["--force"], capture_output=True, text=True,
                          timeout=180, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "--force" in proc.stdout or "forced" in proc.stdout or "anyway" in proc.stdout


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_bench_history_events_record(rounds_dir):
    """--events appends a bench_history record (the vocabulary satellite —
    the doc-drift test in test_timeline covers the docs side)."""
    from distributed_training_pytorch_tpu.telemetry import read_events

    events = str(rounds_dir / "events.jsonl")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_history.py"),
         "--root", str(rounds_dir), "--events", events],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    records = [r for r in read_events(events) if r["event"] == "bench_history"]
    assert len(records) == 1
    assert records[0]["entries"] >= 5
    assert any(s["rounds"][0] <= 2 and s["rounds"][-1] >= 5
               for s in records[0]["streaks"])
