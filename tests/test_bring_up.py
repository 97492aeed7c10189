"""Bring-up guards (PR 21): what let a HEAD that does not import ship, and
what would let a CPU run pass for a chip run.

* every source file is tracked — an ignore rule can no longer swallow a new
  package (``.gitignore``'s unanchored ``data/`` ate ``data/streaming/``);
* the compile cache is placed from outside, never at a path that moves;
* ``chip_smoke.py`` refuses to pass without a TPU.

(The peak-FLOPs table and the OOM classifier have their cases in
``test_telemetry.py::test_device_peak_flops_table`` and
``test_memory.py::test_is_oom_error_classification``.)
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git(*args):
    return subprocess.run(
        ["git", "-C", REPO, *args], capture_output=True, text=True, timeout=60
    )


@pytest.mark.skipif(
    _git("rev-parse", "--is-inside-work-tree").stdout.strip() != "true",
    reason="not a git checkout (the chip tool's copy, an unpacked archive)",
)
def test_no_source_file_is_ignored_or_untracked():
    """`git ls-files --others --ignored` may list caches, the native ``*.so``
    and ``.jax_cache/`` — never a ``*.py`` (or the C++ runtime's source)
    under the package, tests/, scripts/ or examples/. Untracked-but-not-
    ignored sources fail too: whatever git would not commit does not exist
    for the next checkout."""
    roots = ("distributed_training_pytorch_tpu/", "tests/", "scripts/", "examples/")
    ignored = _git("ls-files", "--others", "--ignored", "--exclude-standard").stdout.split("\n")
    untracked = _git("ls-files", "--others", "--exclude-standard").stdout.split("\n")

    def is_source(path):
        return path.startswith(roots) and path.endswith((".py", ".cpp", ".h", "Makefile"))

    swallowed = sorted(p for p in ignored if is_source(p))
    assert not swallowed, f"source files matched by an ignore rule: {swallowed}"
    loose = sorted(p for p in untracked if is_source(p))
    assert not loose, f"source files git does not track (git add them): {loose}"
    # and the ignored set is only what the rules are for
    allowed = ("__pycache__/", ".pyc", ".so", ".jax_cache/", ".pytest_cache/", ".log")
    odd = [p for p in ignored if p.startswith(roots) and p and not any(a in p for a in allowed)]
    assert not odd, f"unexpected ignored files under the source roots: {odd}"


_CACHE_PROBE = """
import json
import jax
from distributed_training_pytorch_tpu.utils.compile_cache import enable_compile_cache
before = jax.config.jax_compilation_cache_dir
returned = enable_compile_cache()
print(json.dumps([before, returned, jax.config.jax_compilation_cache_dir]))
"""


def _run_cache_probe(env_value):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], capture_output=True, text=True,
        env=env, cwd="/", timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_env_var_wins_and_code_sets_nothing(tmp_path):
    placed = str(tmp_path / "placed_cache")
    before, returned, after = _run_cache_probe(placed)
    assert before == placed  # jax took it from the environment by itself
    assert returned == placed and after == placed  # and the code set no other


def test_compile_cache_defaults_to_the_checkout():
    before, returned, after = _run_cache_probe(None)
    assert before is None
    # exactly this fixed path: no temporary name, pid or timestamp in it
    assert returned == after == os.path.join(REPO, ".jax_cache")


def test_chip_smoke_fails_at_once_without_a_tpu():
    """The default invocation under JAX_PLATFORMS=cpu: non-zero within
    seconds, naming the platform it found, and no result line on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=60,
    )
    assert proc.returncode not in (0, 64), proc.stdout + proc.stderr
    assert "'cpu'" in proc.stderr and "not a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
