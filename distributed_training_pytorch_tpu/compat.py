"""The virtual multi-device CPU rig: ``force_host_devices``.

The package targets one installation — jax 0.9.0 (``requirements.txt``) — and
calls ``jax.shard_map``, ``jax.sharding.set_mesh`` /
``get_abstract_mesh`` and ``jax.lax.pcast`` directly; this module holds no
version shim.
"""

from __future__ import annotations

import os

import jax
from jax._src import xla_bridge


def force_host_devices(n: int = 8) -> None:
    """Force an ``n``-device virtual CPU platform (the multi-chip test rig).

    The ONE implementation of the ``--xla_force_host_platform_device_count``
    setup that ``tests/conftest.py``, ``__graft_entry__.py`` and the CPU
    harnesses under ``scripts/`` share: appends the flag to ``XLA_FLAGS``
    (never overwrites caller-supplied flags, and never doubles an existing
    count) and pins the platform to the CPU via both ``JAX_PLATFORMS`` and
    the jax config (the config wins over an environment that was read
    before this call).

    Must run before jax first initializes its CPU client — the backend
    reads ``XLA_FLAGS`` exactly once, at its own first initialization.
    Merely *importing* jax (or this package) does not initialize it, so
    calling this right after imports is safe; calling it after something
    touched ``jax.devices()`` is too late and raises."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={int(n)}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    if xla_bridge.backends_are_initialized():
        if (
            jax.device_count() == int(n)
            and jax.devices()[0].platform == "cpu"
        ):
            return  # already in the requested state — idempotent re-call
        raise RuntimeError(
            "force_host_devices called after the JAX backend initialized — "
            "the device count cannot change anymore; call it before anything "
            "touches jax.devices()"
        )
