"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init — the dry-run sets
``--xla_force_host_platform_device_count=512`` before any import).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis
    carries cross-pod data parallelism over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, found {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"(launch/dryrun.py sets this automatically)")
    dev_array = np.array(devices[:n]).reshape(shape)
    return Mesh(dev_array, axes)


def device_shortfall(what: str, need: int, have: int,
                     platform: str | None = None) -> str:
    """Error text for ``what`` spanning ``need`` devices where only
    ``have`` devices of ``platform`` (default: the default backend) are
    available.  On the CPU backend more host devices can be forced; the
    chips of an accelerator cannot, so the text says to fit the layout
    to them."""
    platform = platform or jax.default_backend()
    msg = (f"{what} spans {need} logical devices but only {have} "
           f"{platform} device(s) are available")
    if platform == "cpu":
        return (f"{msg}; force more host devices (e.g. XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need}, see "
                f"repro.runtime.harness)")
    return f"{msg}; use a layout of at most {have} device(s)"


def make_runtime_mesh(n_devices: int | None = None, axis: str = "dev") -> Mesh:
    """1-D mesh for the communication-plan execution backend
    (``repro.runtime``): one axis over the first ``n_devices`` devices
    of the default backend; HSPMD logical device ids map onto axis
    positions."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if len(devices) < n:
        raise RuntimeError(device_shortfall("the program", n, len(devices)))
    return Mesh(np.array(devices[:n]), (axis,))


def make_smoke_mesh(n_devices: int | None = None,
                    axes=("data", "model")) -> Mesh:
    """Tiny mesh over whatever devices exist (tests: usually 1)."""
    devices = jax.devices()
    n = n_devices or len(devices)
    shape = (1, n) if len(axes) == 2 else (n,)
    return Mesh(np.array(devices[:n]).reshape(shape), axes)
