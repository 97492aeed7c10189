"""Static-analysis subsystem tests (ISSUE 7; docs/static_analysis.md).

Three layers under test: jaxlint's AST rules (each tripped exactly once by
a fixture snippet, with a clean twin that must NOT trip), the waiver
protocol, and the HLO audit (donation aliasing, precision leaks, host
callbacks) — including the acceptance criterion that the shipped engine's
REAL single-step and chained programs donate 100% of param + optimizer-
state input bytes, and the self-parity contract that the shipped codebase
passes the full lint gate with zero unwaived findings.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from distributed_training_pytorch_tpu.analysis import (
    audit_donation,
    audit_host_callbacks,
    audit_precision_leaks,
    build_audit_engine,
    lint_paths,
    lint_source,
    parse_input_output_aliases,
    run_generic,
    run_hlo_audit,
    scan_waivers,
)
from distributed_training_pytorch_tpu.analysis.hlo_audit import (
    count_entry_parameters,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "distributed_training_pytorch_tpu")


def rules_of(result):
    return [f.rule for f in result.unwaived]


# ---------------------------------------------------------------------------
# jaxlint rules: one fixture trips each rule exactly once; a clean twin
# stays silent.
# ---------------------------------------------------------------------------


class TestHostSyncRule:
    def test_float_on_traced_value_trips_once(self):
        src = (
            "import jax\n"
            "def step(state, batch):\n"
            "    loss = batch.sum()\n"
            "    return state, float(loss)\n"
            "stepped = jax.jit(step, donate_argnums=(0,))\n"
        )
        assert rules_of(lint_source(src)) == ["host-sync-in-step"]

    def test_item_and_asarray_each_trip(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def step(state, batch):\n"
            "    return state, (batch.sum().item(), np.asarray(batch))\n"
            "stepped = jax.jit(step, donate_argnums=(0,))\n"
        )
        assert rules_of(lint_source(src)) == ["host-sync-in-step"] * 2

    def test_clean_twin_device_resident_metrics(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def step(state, batch):\n"
            "    return state, {'loss': jnp.mean(batch)}\n"
            "stepped = jax.jit(step, donate_argnums=(0,))\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_static_casts_allowed(self):
        # float()/int() of self-config and shape metadata are trace-time
        # Python, not device syncs.
        src = (
            "import jax\n"
            "class E:\n"
            "    def build(self):\n"
            "        return jax.jit(self._impl, donate_argnums=(0,))\n"
            "    def _impl(self, state, batch):\n"
            "        scale = 1.0 / float(self.accum)\n"
            "        n = int(batch.shape[0])\n"
            "        return state, batch.sum() * scale * n\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_host_code_float_not_flagged(self):
        src = (
            "def log_point(metrics):\n"
            "    return {k: float(v) for k, v in metrics.items()}\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_transitive_callee_is_compiled(self):
        # A helper called from the jitted fn is part of the compiled region.
        src = (
            "import jax\n"
            "def helper(x):\n"
            "    return float(x)\n"
            "def step(state, batch):\n"
            "    return state, helper(batch.sum())\n"
            "stepped = jax.jit(step, donate_argnums=(0,))\n"
        )
        assert rules_of(lint_source(src)) == ["host-sync-in-step"]


class TestWallClockRule:
    def test_time_time_in_scan_body_trips_once(self):
        src = (
            "import jax, time\n"
            "def sweep(xs):\n"
            "    def body(carry, x):\n"
            "        return carry + x, time.time()\n"
            "    return jax.lax.scan(body, 0.0, xs)\n"
            "swept = jax.jit(sweep)\n"
        )
        assert rules_of(lint_source(src)) == ["wall-clock-in-step"]

    def test_clean_twin_host_timing(self):
        src = (
            "import time\n"
            "def train_epoch():\n"
            "    t0 = time.perf_counter()\n"
            "    return time.perf_counter() - t0\n"
        )
        assert rules_of(lint_source(src)) == []


class TestRankGateRule:
    UNGATED = (
        "def dump(path, lines):\n"
        "    with open(path, 'w') as f:\n"
        "        f.writelines(lines)\n"
    )

    def test_ungated_write_trips_once(self):
        assert rules_of(lint_source(self.UNGATED)) == [
            "file-write-without-rank-gate"
        ]

    def test_gated_twin_clean(self):
        src = (
            "import jax\n"
            "def dump(path, lines):\n"
            "    if jax.process_index() != 0:\n"
            "        return\n"
            "    with open(path, 'w') as f:\n"
            "        f.writelines(lines)\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_enabled_class_convention_clean(self):
        # The EventLog pattern: the class establishes self.enabled from a
        # process-index compare; methods write under that contract.
        src = (
            "import jax\n"
            "class Log:\n"
            "    def __init__(self, path):\n"
            "        proc = jax.process_index()\n"
            "        self.enabled = path is not None and proc == 0\n"
            "        self._path = path\n"
            "    def _open(self):\n"
            "        return open(self._path, 'a')\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_read_mode_never_flagged(self):
        src = "def load(p):\n    return open(p).read()\n"
        assert rules_of(lint_source(src)) == []


class TestCrossThreadRule:
    def test_unlocked_mutation_trips_once(self):
        src = (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self.count = 0\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        self.count += 1\n"
        )
        assert rules_of(lint_source(src)) == ["cross-thread-mutation-without-lock"]

    def test_locked_twin_clean(self):
        src = (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self.count = 0\n"
            "        self._lock = threading.Lock()\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        with self._lock:\n"
            "            self.count += 1\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_transitive_class_callee_checked(self):
        src = (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self.done = False\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        self._finish()\n"
            "    def _finish(self):\n"
            "        self.done = True\n"
        )
        assert rules_of(lint_source(src)) == ["cross-thread-mutation-without-lock"]

    def test_threadless_class_clean(self):
        src = (
            "class Plain:\n"
            "    def bump(self):\n"
            "        self.count = 1\n"
        )
        assert rules_of(lint_source(src)) == []


class TestBareExceptRule:
    def test_bare_except_trips_once(self):
        src = "try:\n    x = 1\nexcept:\n    pass\n"
        assert rules_of(lint_source(src)) == ["bare-except"]

    def test_except_exception_clean(self):
        src = "try:\n    x = 1\nexcept Exception:\n    pass\n"
        assert rules_of(lint_source(src)) == []

    def test_bare_except_with_reraise_clean(self):
        src = "try:\n    x = 1\nexcept:\n    raise\n"
        assert rules_of(lint_source(src)) == []


class TestZipStrictRule:
    def test_tree_leaf_zip_without_strict_trips_once(self):
        src = (
            "import jax\n"
            "def pair(a, b):\n"
            "    return list(zip(jax.tree.leaves(a), jax.tree.leaves(b)))\n"
        )
        assert rules_of(lint_source(src)) == ["zip-no-strict"]

    def test_leaves_named_iterables_trip(self):
        # The PR 9 bug shape: pre-flattened leaf lists, zipped lazily.
        src = (
            "def pair(leaves_a, leaves_b):\n"
            "    return list(zip(leaves_a, leaves_b))\n"
        )
        assert rules_of(lint_source(src)) == ["zip-no-strict"]

    def test_strict_true_twin_clean(self):
        src = (
            "import jax\n"
            "def pair(a, b):\n"
            "    return list(zip(jax.tree.leaves(a), jax.tree.leaves(b), "
            "strict=True))\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_strict_false_documents_truncation(self):
        src = (
            "import jax\n"
            "def pair(a, b):\n"
            "    return list(zip(jax.tree.leaves(a), jax.tree.leaves(b), "
            "strict=False))\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_non_tree_zip_is_generic_layers_business(self):
        src = (
            "def pair(xs, ys):\n"
            "    return list(zip(xs, ys))\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_starred_transpose_clean(self):
        src = (
            "import jax\n"
            "def t(rows):\n"
            "    return list(zip(*(jax.tree.leaves(r) for r in rows)))\n"
        )
        assert rules_of(lint_source(src)) == []


class TestMissingDonateRule:
    def test_state_jit_without_donate_trips_once(self):
        src = (
            "import jax\n"
            "def step(state, batch):\n"
            "    return state\n"
            "stepped = jax.jit(step)\n"
        )
        assert rules_of(lint_source(src)) == ["missing-donate-on-jit"]

    def test_donated_twin_clean(self):
        src = (
            "import jax\n"
            "def step(state, batch):\n"
            "    return state\n"
            "stepped = jax.jit(step, donate_argnums=(0,))\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_stateless_jit_clean(self):
        src = (
            "import jax\n"
            "def apply(params, x):\n"
            "    return x\n"
            "applied = jax.jit(apply)\n"
        )
        assert rules_of(lint_source(src)) == []

    def test_decorator_form_trips_once(self):
        src = (
            "import functools, jax\n"
            "@functools.partial(jax.jit, static_argnums=(1,))\n"
            "def step(state, n):\n"
            "    return state\n"
        )
        assert rules_of(lint_source(src)) == ["missing-donate-on-jit"]


# ---------------------------------------------------------------------------
# Waivers
# ---------------------------------------------------------------------------


class TestWaivers:
    VIOLATION = (
        "def dump(path):\n"
        "    with open(path, 'w') as f:  "
        "# jaxlint: disable=file-write-without-rank-gate -- {reason}\n"
        "        f.write('x')\n"
    )

    def test_reasoned_waiver_suppresses(self):
        res = lint_source(self.VIOLATION.format(reason="single-process CLI"))
        assert res.unwaived == []
        assert len(res.waived) == 1
        assert res.waived[0].waiver_reason == "single-process CLI"
        assert res.unused_waivers == []

    def test_waiver_without_reason_does_not_waive(self):
        src = (
            "def dump(path):\n"
            "    with open(path, 'w') as f:  "
            "# jaxlint: disable=file-write-without-rank-gate\n"
            "        f.write('x')\n"
        )
        res = lint_source(src)
        assert sorted(rules_of(res)) == [
            "file-write-without-rank-gate",
            "waiver-missing-reason",
        ]

    def test_waiver_for_other_rule_does_not_apply(self):
        src = (
            "def dump(path):\n"
            "    with open(path, 'w') as f:  "
            "# jaxlint: disable=bare-except -- wrong rule\n"
            "        f.write('x')\n"
        )
        res = lint_source(src)
        assert rules_of(res) == ["file-write-without-rank-gate"]
        assert len(res.unused_waivers) == 1

    def test_scan_waivers_parses_multi_rule(self):
        waivers = scan_waivers(
            "x = 1  # jaxlint: disable=bare-except,host-sync-in-step -- why\n"
        )
        assert waivers[1].rules == ("bare-except", "host-sync-in-step")
        assert waivers[1].reason == "why"


# ---------------------------------------------------------------------------
# HLO audit primitives
# ---------------------------------------------------------------------------


def _compile(fn, args, **jit_kwargs):
    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


class TestDonationAudit:
    STATE = {
        "w": jax.ShapeDtypeStruct((128, 64), jnp.float32),
        "m": jax.ShapeDtypeStruct((128, 64), jnp.float32),
    }
    BATCH = jax.ShapeDtypeStruct((8, 64), jnp.float32)

    @staticmethod
    def _fn(state, batch):
        return (
            {"w": state["w"] + batch.sum(), "m": state["m"] * 0.9},
            batch.mean(),
        )

    def test_donated_program_fully_aliased(self):
        compiled = _compile(self._fn, (self.STATE, self.BATCH), donate_argnums=(0,))
        report = audit_donation(
            compiled, (self.STATE, self.BATCH), must_donate=lambda p: "[0]" in p
        )
        assert report.ok
        assert report.donated_fraction == 1.0
        assert report.audited_bytes == 2 * 128 * 64 * 4

    def test_undonated_program_reports_exact_bytes(self):
        compiled = _compile(self._fn, (self.STATE, self.BATCH))
        assert parse_input_output_aliases(compiled.as_text()) == set()
        report = audit_donation(
            compiled, (self.STATE, self.BATCH), must_donate=lambda p: "[0]" in p
        )
        assert not report.ok
        assert report.undonated_bytes == 2 * 128 * 64 * 4
        assert "UNDONATED" in report.describe()

    def test_entry_parameter_count_matches_leaves(self):
        compiled = _compile(self._fn, (self.STATE, self.BATCH), donate_argnums=(0,))
        assert count_entry_parameters(compiled.as_text()) == 3

    def test_leaf_mapping_mismatch_refuses(self):
        compiled = _compile(self._fn, (self.STATE, self.BATCH), donate_argnums=(0,))
        with pytest.raises(ValueError, match="cannot map"):
            audit_donation(compiled, (self.STATE, self.BATCH, self.BATCH))


class TestPrecisionAudit:
    def test_bf16_program_clean(self):
        w = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)
        x = jax.ShapeDtypeStruct((8, 64), jnp.bfloat16)
        lowered = jax.jit(lambda w, x: jnp.dot(x, w)).lower(w, x)
        report = audit_precision_leaks(lowered.as_text(), policy="bf16")
        assert report.ok and report.mxu_ops == 1

    def test_f32_dot_is_a_leak(self):
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        lowered = jax.jit(lambda w, x: jnp.dot(x, w)).lower(w, x)
        report = audit_precision_leaks(lowered.as_text(), policy="bf16")
        assert not report.ok
        assert report.leaks[0]["category"] == "matmul"
        assert report.leaks[0]["result_type"].endswith("f32")

    def test_zero_mxu_ops_is_not_a_pass(self):
        # A parse/workload regression must not pass vacuously.
        report = audit_precision_leaks("module @empty {}", policy="bf16")
        assert not report.ok
        assert "vacuous" in report.describe()


class TestCallbackAudit:
    def test_clean_program(self):
        x = jax.ShapeDtypeStruct((8,), jnp.float32)
        compiled = _compile(lambda x: x * 2.0, (x,))
        assert audit_host_callbacks(compiled.as_text()).ok

    def test_callback_markers_detected(self):
        text = (
            'ENTRY %main { %t = token[] after-all()\n'
            '%i = (f32[8], token[]) infeed(token[] %t)\n'
            '%c = f32[8] custom-call(), custom_call_target='
            '"xla_python_cpu_callback" }'
        )
        report = audit_host_callbacks(text)
        assert not report.ok
        assert "infeed" in report.hits
        assert any("callback" in h for h in report.hits)


# ---------------------------------------------------------------------------
# The shipped engine programs (acceptance criterion) + self-parity
# ---------------------------------------------------------------------------


class TestEngineDonationParity:
    def test_single_and_chained_programs_donate_all_state_bytes(self):
        # ISSUE 7 acceptance: 100% of param + optimizer-state input bytes
        # aliased in BOTH the single-step and chained (chain_steps>1)
        # compiled programs.
        report = run_hlo_audit(chain_steps=3)
        assert report.single.ok and report.single.donated_fraction == 1.0
        assert report.chained.ok and report.chained.donated_fraction == 1.0
        assert report.single.audited_bytes > 0
        # params AND opt_state both actually audited (not vacuously).
        roles = {e["role"] for e in report.single.entries if e["must_donate"]}
        assert roles == {"params", "opt_state"}
        assert report.precision.ok
        assert report.callbacks.ok
        assert report.ok

    def test_injected_violation_fails(self):
        report = run_hlo_audit(chain_steps=3, inject_violation=True)
        assert not report.ok
        assert not report.single.ok and not report.chained.ok
        assert report.single.undonated_bytes == report.single.audited_bytes
        # ISSUE 10 satellite: the injected-violation self-test covers the
        # SHARDED path too — an undonated SPMD program must fail its audit.
        assert report.sharded
        assert not report.sharded_single.ok and not report.sharded_chained.ok

    def test_sharded_programs_donate_all_state_bytes(self):
        # ISSUE 10 satellite: 100% param+opt-state donation and no
        # precision leaks must hold under SPMD partitioning (the 8-device
        # conftest platform always runs the sharded audit), and the audited
        # state must be GENUINELY sharded — fsdp and tensor specs both
        # present — or the pass would be vacuous.
        from distributed_training_pytorch_tpu.analysis.hlo_audit import (
            _AUDIT_FSDP_MIN_SIZE,
            _AUDIT_SHARDING_RULES,
            _audit_mesh,
            build_audit_engine,
        )

        report = run_hlo_audit(chain_steps=3)
        assert report.sharded
        assert report.sharded_single.ok
        assert report.sharded_single.donated_fraction == 1.0
        assert report.sharded_chained.ok
        assert report.sharded_chained.donated_fraction == 1.0
        assert report.sharded_precision.ok
        engine, state, _ = build_audit_engine(
            mesh=_audit_mesh(),
            sharding_rules=_AUDIT_SHARDING_RULES,
            fsdp_min_size=_AUDIT_FSDP_MIN_SIZE,
        )
        specs = [
            str(s.spec)
            for s in jax.tree.leaves(
                engine.state_sharding_tree(state),
                is_leaf=lambda x: hasattr(x, "spec"),
            )
        ]
        assert any("fsdp" in s for s in specs), specs
        assert any("tensor" in s for s in specs), specs

    def test_chained_probe_matches_real_dispatch_program(self):
        # The audit's chained probe (no trace-count side effects) and the
        # REAL dispatch program (engine._chained_step_fn) are two
        # constructions of the same window: pin their lowered HLO equal so
        # a change to one cannot silently leave the audit verifying a
        # program the trainer no longer runs.
        length = 3
        engine, state, batch = build_audit_engine()
        window = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((length,) + tuple(x.shape), x.dtype),
            batch,
        )
        probe_text = engine.lower_step_probe(
            state, window, donate=True, chain_length=length
        ).as_text()
        real_fn = engine._chained_step_fn(length, state)
        with engine._ambient_mesh():
            real_text = real_fn.lower(state, window).as_text()
        assert probe_text == real_text

    def test_donate_state_false_engine_audits_undonated(self):
        # The probe mirrors the dispatch path's donation flag: an engine
        # built with donate_state=False runs undonated programs, and the
        # audit must see (and fail on) exactly that program.
        import optax

        from distributed_training_pytorch_tpu.train import TrainEngine

        engine, state, batch = build_audit_engine()
        plain = TrainEngine(
            engine.loss_fn, optax.sgd(0.05, momentum=0.9), engine.mesh,
            donate_state=False,
        )
        compiled = plain.compile_step_probe(state, batch, donate=True)
        report = audit_donation(compiled, (state, batch))
        assert not report.ok
        assert report.undonated_bytes == report.audited_bytes

    def test_probe_memoized_and_keyed_by_donate(self):
        engine, state, batch = build_audit_engine()
        a = engine.compile_step_probe(state, batch, donate=True)
        b = engine.compile_step_probe(state, batch, donate=True)
        c = engine.compile_step_probe(state, batch)  # undonated default
        assert a is b
        assert a is not c
        assert parse_input_output_aliases(c.as_text()) == set()


class TestSelfParity:
    def test_package_passes_jaxlint(self):
        res = lint_paths([PACKAGE])
        assert res.unwaived == [], "\n".join(f.describe() for f in res.unwaived)
        # Every waiver in the shipped tree is used and carries a reason.
        assert res.unused_waivers == []
        assert all(f.waiver_reason for f in res.waived)

    def test_repo_passes_generic_layer(self):
        paths = [PACKAGE] + [
            os.path.join(REPO, p)
            for p in ("scripts", "tests", "examples", "bench.py")
        ]
        report = run_generic([p for p in paths if os.path.exists(p)])
        assert report.ok, "\n".join(f.describe() for f in report.findings)


@pytest.mark.slow  # subprocess runs of the CLI that verify.sh stage 2 already drives (PR 21)
class TestStaticAuditCLI:
    def _run(self, *flags):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "static_audit.py"),
             *flags],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            cwd=REPO, timeout=300,
        )

    def test_source_passes_exit_zero_and_emit_event(self, tmp_path):
        events = tmp_path / "events.jsonl"
        proc = self._run("--skip-hlo", "--skip-comm", "--events", str(events))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        from distributed_training_pytorch_tpu.telemetry import read_events

        records = [e for e in read_events(str(events))
                   if e["event"] == "static_audit"]
        assert len(records) == 1
        assert records[0]["passed"] is True
        assert records[0]["lint_findings"] == 0
        assert records[0]["lint_waived"] >= 1

    def test_injected_lint_violation_fails(self):
        proc = self._run("--skip-hlo", "--skip-comm", "--inject-violation", "lint")
        assert proc.returncode == 2, proc.stdout + proc.stderr
        # every rule tripped at least once in the synthetic module
        from distributed_training_pytorch_tpu.analysis import RULES

        for rule in RULES:
            if rule == "waiver-missing-reason":
                continue
            assert rule in proc.stdout, f"{rule} not tripped:\n{proc.stdout}"

    def test_unused_waiver_reported_and_still_exits_zero(self, tmp_path):
        # ISSUE 11 satellite: the CLI's unused-waiver reporting path. A
        # waiver whose finding is gone is a NOTE (delete-the-comment nudge),
        # never a failure — via --lint-path, the CLI's lint-a-known-tree
        # seam (the shipped package can't carry one: self-parity forbids it).
        mod = tmp_path / "stale.py"
        mod.write_text(
            "x = 1  # jaxlint: disable=bare-except -- fixed long ago\n"
        )
        proc = self._run("--skip-hlo", "--skip-comm", "--lint-path", str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "NOTE unused waiver" in proc.stdout
        assert f"{mod}:1" in proc.stdout
        assert "bare-except" in proc.stdout

    def test_waived_finding_printed_with_reason(self, tmp_path):
        mod = tmp_path / "waived.py"
        mod.write_text(
            "def dump(path):\n"
            "    with open(path, 'w') as f:  "
            "# jaxlint: disable=file-write-without-rank-gate -- test CLI\n"
            "        f.write('x')\n"
        )
        proc = self._run("--skip-hlo", "--skip-comm", "--lint-path", str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "[waived: test CLI]" in proc.stdout

    def test_comm_injection_flag_conflicts_refused_fast(self):
        # perf_gate discipline: flag conflicts fail BEFORE any work.
        proc = self._run("--inject-violation", "comm", "--skip-comm")
        assert proc.returncode == 2
        assert "requires the comm pass" in proc.stderr
        proc = self._run("--inject-violation", "hlo", "--skip-hlo")
        assert proc.returncode == 2
        assert "requires the HLO pass" in proc.stderr
        proc = self._run("--update-comm-baseline", "--inject-violation", "lint")
        assert proc.returncode == 2
        assert "must not record" in proc.stderr
