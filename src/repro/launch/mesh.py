"""Device topology: mesh construction and phase device assignment.

Functions (not module-level constants) so importing this module never
touches jax device state.  Two concerns live here:

* **Meshes** for params/cache sharding.  Single pod: 16x16 = 256 chips
  (v5e pod), axes (data, model).  Multi-pod: 2 pods = 512 chips, axes
  (pod, data, model); 'pod' is the outer data-parallel axis whose
  collectives cross DCN.
* **Phase device assignment** for disaggregated serving
  (:class:`DeviceAssignment`): enumerate the visible devices and pin the
  prefill and decode engines to *distinct* devices when the host has at
  least two, degrading gracefully to a single shared device otherwise.
  On CPU-only hosts (CI, dev containers) set
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
  first jax import to split the host into N logical devices — the
  multi-device hand-off path is then exercised everywhere, not just on
  accelerator fleets.
"""
from __future__ import annotations

import dataclasses
import os
from typing import List, Optional

import numpy as np

import jax
from jax.sharding import Mesh

# the env var + flag that fakes a multi-device host on CPU; quoted in
# error messages so a single-device failure tells the user how to get
# the multi-device path locally
MULTI_DEVICE_HINT = ("set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                     "BEFORE the first jax import to split a CPU host into "
                     "N logical devices")


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devs)} are "
            f"visible. For the dry-run, set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=512 BEFORE importing "
            f"jax (launch/dryrun.py does this).")
    return jax.make_mesh(shape, axes, devices=devs[:need])


def make_host_mesh() -> Mesh:
    """Degenerate 1x1 mesh for CPU smoke tests / examples."""
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


# --------------------------------------------------- phase device assignment
@dataclasses.dataclass(frozen=True)
class DeviceAssignment:
    """Which physical device each serving phase's engine lives on.

    ``prefill`` and ``decode`` are jax Devices; ``distinct`` is the one
    bit the hand-off path branches on — when False both engines share
    one device and the page transfer is a (nearly free) same-device
    ``device_put``, when True the transfer crosses a real device
    boundary and the async hand-off has actual latency to hide.
    """
    prefill: jax.Device
    decode: jax.Device

    @property
    def distinct(self) -> bool:
        return self.prefill != self.decode

    def summary(self) -> str:
        tag = "distinct" if self.distinct else "shared"
        return (f"prefill -> {device_label(self.prefill)}, "
                f"decode -> {device_label(self.decode)} ({tag})")


def device_label(dev: jax.Device) -> str:
    """Stable human/cache-readable name for one device, e.g. ``cpu:1``."""
    return f"{dev.platform}:{dev.id}"


def visible_devices(backend: Optional[str] = None) -> List[jax.Device]:
    """The devices a phase engine may be pinned to (jax.devices, but
    behind a function so tests can reason about the call site)."""
    return jax.devices(backend) if backend else jax.devices()


def device_assignment(*, prefill_index: Optional[int] = None,
                      decode_index: Optional[int] = None,
                      backend: Optional[str] = None) -> DeviceAssignment:
    """Pin the two serving phases to devices.

    Default policy: with >= 2 visible devices, prefill takes device 0
    and decode device 1 (distinct, so the hand-off pipeline has a real
    boundary to overlap); with one device both phases share it — the
    code path is identical, the transfer is just free.  Explicit
    ``prefill_index`` / ``decode_index`` override the policy; an
    out-of-range index raises with the ``XLA_FLAGS`` hint rather than
    silently colocating.
    """
    devs = visible_devices(backend)
    if not devs:
        raise RuntimeError("no jax devices visible")

    def pick(idx: Optional[int], default: int, phase: str) -> jax.Device:
        if idx is None:
            idx = default if default < len(devs) else 0
        if not 0 <= idx < len(devs):
            raise ValueError(
                f"{phase} device index {idx} out of range: only "
                f"{len(devs)} device(s) visible ({MULTI_DEVICE_HINT})")
        return devs[idx]

    return DeviceAssignment(prefill=pick(prefill_index, 0, "prefill"),
                            decode=pick(decode_index, 1, "decode"))


def forced_host_device_env(n: int) -> dict:
    """Environment overlay that makes a *subprocess* see ``n`` CPU
    devices (the in-process backend is already initialized, so the flag
    only helps processes launched after it is set)."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    env["XLA_FLAGS"] = f"{flags} {flag}".strip()
    return env
