"""Mesh and ``shard_map`` call sites shared across the repo (DESIGN.md §6).

Every mesh construction in the repo (production meshes, tests' virtual-
device meshes, elastic restarts) goes through ``make_mesh_compat``, and
every kernel-launching ``shard_map`` through ``shard_map_norep``, so the
mesh axis types and the replication-check setting live in one place.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

shard_map = jax.shard_map


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with the replication checker disabled.

    ``pallas_call`` has no replication rule, so any shard_map body that
    launches a kernel (the distributed fused path, DESIGN.md §7) must turn
    the checker off.
    """
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def make_mesh_compat(
    shape: Sequence[int],
    axes: Sequence[str],
    *,
    devices: Optional[Sequence] = None,
):
    """``jax.make_mesh`` with every axis of type ``AxisType.Auto``."""
    kwargs = {"axis_types": (AxisType.Auto,) * len(tuple(axes))}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(shape), tuple(axes), **kwargs)


_REEXEC_SENTINEL = "_REPRO_ENSURE_DEVICES_REEXEC"


def ensure_host_devices(n: int) -> None:
    """Re-exec the current script with ``n`` emulated host devices.

    jax's platform (and device count) freezes at import time, so a CLI
    flag like the examples' ``--sharded`` can only be honored on a
    single-device host by restarting the interpreter with ``XLA_FLAGS``
    set first. Safety rails:

    * the device-count flag is APPENDED — XLA takes the last occurrence
      of a repeated flag, so an inherited lower count cannot win;
    * a sentinel env var guards against an exec loop: if the re-exec'd
      process STILL lacks ``n`` devices (e.g. a non-CPU platform ignores
      host-device emulation), it raises instead of exec'ing forever.
    """
    import os
    import sys

    if jax.device_count() >= n:
        return
    if os.environ.get(_REEXEC_SENTINEL):
        raise RuntimeError(
            f"re-exec with --xla_force_host_platform_device_count={n} "
            f"still sees {jax.device_count()} device(s) — platform "
            f"{jax.default_backend()!r} does not support host-device "
            "emulation; run on a CPU backend (JAX_PLATFORMS=cpu) or a "
            f"host with >= {n} devices"
        )
    env = dict(os.environ)
    env[_REEXEC_SENTINEL] = "1"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}"
                        ).strip()
    os.execve(sys.executable, [sys.executable] + sys.argv, env)
