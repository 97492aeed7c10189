"""Autotuner core (train/autotune.py) + the ONE shared timing implementation.

The ranking/refusal/keep logic is unit-tested on authored measurements (the
end-to-end sweep including the injected-known-win seam runs in verify.sh
stage 15 via ``scripts/autotune.py --self-test``); the shared scan-chain
timer is exercised for real and AST-enforced against private copies in
``scripts/resnet_pallas_probe.py`` (the test_run_compare.py satellite
pattern).
"""

import ast
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.telemetry.history import FLAT_REL_TOL
from distributed_training_pytorch_tpu.train import autotune as autotune_lib
from distributed_training_pytorch_tpu.train.engine import xla_flag_options

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


# ---------------------------------------------------------------------------
# the one timing implementation
# ---------------------------------------------------------------------------


def test_time_chained_measures_a_real_function():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 16), jnp.float32)

    def f(x, w):
        return jnp.tanh(x @ w)

    dt = autotune_lib.time_chained(f, x, w, steps=4, windows=2)
    # Differencing of noisy sub-ms windows can land at ~0; it must at least
    # be a finite float and not wildly negative (window noise bound).
    assert np.isfinite(dt)
    assert dt > -1e-3


def test_probe_imports_the_shared_timer_and_keeps_no_private_copy():
    """Satellite 1, test-enforced: resnet_pallas_probe.py imports
    train.autotune.time_chained and defines NO local timing twin."""
    path = os.path.join(REPO, "scripts", "resnet_pallas_probe.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename="resnet_pallas_probe.py")
    imports_timer = any(
        isinstance(node, ast.ImportFrom)
        and node.module
        and node.module.endswith("train.autotune")
        and any(alias.name == "time_chained" for alias in node.names)
        for node in ast.walk(tree)
    )
    assert imports_timer, (
        "the probe must import train.autotune.time_chained (the ONE "
        "two-length-differencing timer)"
    )
    local_defs = [
        node.name for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and ("time_chained" in node.name or "timed" in node.name)
    ]
    assert not local_defs, (
        f"the probe defines a private timer {local_defs} — the timing "
        "implementation lives in train/autotune.py only"
    )


# ---------------------------------------------------------------------------
# ranking / refusal / keep rule
# ---------------------------------------------------------------------------

_CATS_BASE = {"convolution": 0.5, "matmul": 0.2, "other": 0.1, "idle": 0.2}
_CATS_FAST = {"convolution": 0.55, "matmul": 0.22, "other": 0.13, "idle": 0.1}


def _prov(**over):
    prov = {"jax": "0.9", "jaxlib": "0.9", "xla_flags": "", "mesh": None,
            "dtype": "float32", "chain_steps": 4, "batch": 64}
    prov.update(over)
    return prov


def _meas(step_ms, *, cats=None, prov=None):
    m = {"step_ms": step_ms, "chain_steps": 4, "windows": 3}
    if cats is not None:
        m["categories"] = cats
    if prov is not None:
        m["provenance"] = prov
    return m


def _baseline(step_ms=10.0):
    return {"name": "baseline", "knobs": {},
            "measurement": _meas(step_ms, cats=_CATS_BASE, prov=_prov())}


def test_rank_orders_by_metric_and_attributes_the_delta():
    results = [
        {"name": "slow", "knobs": {"chain_steps": 8},
         "measurement": _meas(11.0, cats=_CATS_BASE, prov=_prov(chain_steps=8))},
        {"name": "fast", "knobs": {"xla_flags": "--xla_x=1"},
         "measurement": _meas(8.0, cats=_CATS_FAST,
                              prov=_prov(xla_flags="--xla_x=1"))},
    ]
    report = autotune_lib.rank_candidates(_baseline(), results)
    assert [e["name"] for e in report["ranked"]] == ["fast", "slow"]
    assert report["refused"] == []
    winner = report["ranked"][0]
    assert winner["delta_ms"] == pytest.approx(-2.0)
    # attribution rows come from profiling.diff and must cover the delta
    assert winner["attribution"], "categories on both sides -> rows required"
    total = sum(row["delta"] for row in winner["attribution"])
    assert total == pytest.approx(-2.0, abs=0.2)
    assert sum(row["frac_of_delta"] for row in winner["attribution"]) == (
        pytest.approx(1.0, abs=0.02))
    assert winner["attribution_text"]
    assert report["kept"] is True and report["winner"]["name"] == "fast"


def test_undeclared_provenance_drift_is_refused_not_ranked():
    """The PR 14 rule, sweep-adapted: a facet the candidate did not declare
    as swept (here dtype) refuses the comparison; a declared one (here
    chain_steps) is allowed."""
    results = [
        {"name": "dtype-drift", "knobs": {"chain_steps": 8},
         "measurement": _meas(7.0, cats=_CATS_FAST,
                              prov=_prov(chain_steps=8, dtype="bfloat16"))},
        {"name": "declared", "knobs": {"chain_steps": 8},
         "measurement": _meas(9.0, cats=_CATS_FAST, prov=_prov(chain_steps=8))},
    ]
    report = autotune_lib.rank_candidates(_baseline(), results)
    assert [r["name"] for r in report["refused"]] == ["dtype-drift"]
    assert report["refused"][0]["differing_keys"] == ["dtype"]
    # the refused (faster!) candidate must not leak into the ranking
    assert [e["name"] for e in report["ranked"]] == ["declared"]
    assert report["winner"]["name"] == "declared"


def test_sub_noise_win_is_not_kept():
    """A 'win' inside the flat-streak band (FLAT_REL_TOL) would re-flatten
    the bench line next round — ranked, but kept=False, winner=None."""
    inside = 10.0 * (1.0 - FLAT_REL_TOL / 2)
    results = [{"name": "noise", "knobs": {},
                "measurement": _meas(inside, cats=_CATS_BASE, prov=_prov())}]
    report = autotune_lib.rank_candidates(_baseline(), results)
    assert report["ranked"] and report["kept"] is False
    assert report["winner"] is None


def test_missing_categories_rank_without_attribution():
    results = [{"name": "blind", "knobs": {},
                "measurement": _meas(8.0, prov=_prov())}]
    report = autotune_lib.rank_candidates(_baseline(), results)
    entry = report["ranked"][0]
    assert entry["attribution"] is None and entry["attribution_text"] == ""


# ---------------------------------------------------------------------------
# TUNED.json round-trip
# ---------------------------------------------------------------------------


def _kept_report():
    results = [{"name": "fast", "knobs": {"chain_steps": 8, "xla_flags": "--xla_y=1"},
                "measurement": _meas(8.0, cats=_CATS_FAST,
                                     prov=_prov(chain_steps=8,
                                                xla_flags="--xla_y=1"))}]
    return autotune_lib.rank_candidates(_baseline(), results)


def test_emitted_tuned_json_is_a_kept_sweep_with_attribution(tmp_path):
    """An emitted TUNED.json IS the evidence artifact: it round-trips, and a
    kept winner ships with per-category attribution and a declared-knobs
    grammar. Nothing reads it back into a run (no ``TUNED=1`` opt-in)."""
    path = str(tmp_path / "TUNED.json")
    report = _kept_report()
    autotune_lib.emit_tuned(path, report)
    data = autotune_lib.load_tuned(path)
    assert data == json.loads(json.dumps(report))
    assert data["schema"] == 1 and data["kept"] is True
    winner = data["winner"]
    assert winner["delta_ms"] < 0
    assert winner["attribution"], "a kept win ships WITH its attribution"
    assert set(winner["knobs"]) <= {"xla_flags", "chain_steps", "batch",
                                    "accum_steps", "pallas", "block_rows"}
    for entry in data["ranked"]:
        assert "measurement" in entry and "delta_ms" in entry
    # absent / unreadable files load as None, never a crash
    assert autotune_lib.load_tuned(str(tmp_path / "nope.json")) is None
    assert not hasattr(autotune_lib, "tuned_defaults")


# ---------------------------------------------------------------------------
# the XLA-flag -> per-compile compiler-options bridge
# ---------------------------------------------------------------------------


def test_xla_flag_options_parses_the_flag_grammar():
    assert xla_flag_options("--xla_a=true --xla_b=2") == {
        "xla_a": "true", "xla_b": "2"}
    assert xla_flag_options("--xla_bare") == {"xla_bare": "true"}
    assert xla_flag_options("") == {}
    assert xla_flag_options(None) == {}
    with pytest.raises(ValueError):
        xla_flag_options("xla_no_dashes=1")
    with pytest.raises(ValueError):
        xla_flag_options("--not_an_xla_flag=1")
