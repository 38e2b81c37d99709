"""Which device a process runs on.  Imports no jax at module scope, so a
parent that must stay off the chip (``chip_smoke.py``, ``bin/client``)
can import it freely.

Supported installation: jax/jaxlib ``SUPPORTED_JAX`` with libtpu (the
CI workflow pins the same jax).

**One platform rule.**  ``JAX_PLATFORMS`` is the only switch:

* ``JAX_PLATFORMS=cpu`` — set by the caller (tests, CI, the tier-1
  command, CPU peers that share a host with the chip's owner) — means
  the CPU, on purpose.
* anything else (unset, ``tpu``, ``tpu,cpu``) means the TPU: every entry
  point that dispatches to a device calls :func:`require_device_platform`
  before it binds a port or builds a mesh, and exits non-zero unless
  ``jax.default_backend() == "tpu"``.  jax registers the TPU backend to
  fail quietly when ``JAX_PLATFORMS`` is unset — a missing chip, or one
  held by another process, would otherwise be served from the CPU
  without a word.

A chip belongs to one process at a time: valid layouts are one
``--device-step`` server, an in-process cluster, or a TCP cluster with
one device-owning server per chip and every other process (clients
included) under ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import os
import re
from typing import Dict

SUPPORTED_JAX = "0.9.0"


def cpu_requested() -> bool:
    """Did the caller ask for the CPU (``JAX_PLATFORMS=cpu``)?"""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def device_report() -> Dict[str, object]:
    """The device as jax reports it — carried by every banner, metrics
    snapshot and bench row so a reader can tell what served.  Touches
    the backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_device_platform(entry: str) -> Dict[str, object]:
    """The platform rule for a device entry point: returns
    :func:`device_report` on the CPU when the caller asked for it and on
    the TPU otherwise; exits non-zero naming what it found in its
    place."""
    import jax

    try:
        backend = jax.default_backend()
    except RuntimeError as exc:
        # JAX_PLATFORMS names the tpu and its backend failed to start:
        # no chip on this machine, or another process holds it
        raise SystemExit(
            f"{entry}: the TPU backend did not initialise (no chip, or "
            f"the chip is held by another process): {exc}"
        ) from exc
    if backend != "tpu" and not cpu_requested():
        raise SystemExit(
            f"{entry}: needs the TPU but jax fell back to {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}: no chip "
            "on this machine, or the chip is held by another process); "
            "set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    return device_report()


def force_cpu_platform(n_devices: int | None = None) -> None:
    """Select the CPU from inside the process, optionally as ``n``
    virtual devices: sets ``JAX_PLATFORMS=cpu`` (children inherit it)
    and the matching jax config.  For code that is CPU-only by design —
    the test suite's 8-device virtual mesh, simulation sweeps, ordering
    pool workers, ``bench.py --smoke``.  Call it before the first
    backend touch."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            os.environ.get("XLA_FLAGS", ""),
        )
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
