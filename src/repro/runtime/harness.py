"""Multi-CPU-device harness.

jax locks the host device count at first backend init, so anything that
needs N > 1 devices must either set ``XLA_FLAGS`` before importing jax
(:func:`ensure_host_devices`) or run in a child process with the flag in
its environment (:func:`run_subprocess` — the pattern the test suite uses
so the main pytest process keeps seeing one device, per the dry-run spec).
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

FORCE_FLAG = "--xla_force_host_platform_device_count"
DEFAULT_DEVICES = 8


def _repo_root() -> str:
    # src/repro/runtime/harness.py -> repo root
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory: the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads
    that variable itself, and nothing else is set), else ``.jax_cache``
    at the root of this checkout.  The path is part of what makes an
    entry findable by a later process, so it is fixed.  Call it before
    the process's first compile: JAX fixes the cache when it first uses
    it."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_repo_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def host_device_env(n_devices: int = DEFAULT_DEVICES,
                    base: dict | None = None) -> dict:
    """Environment for a child process that must see ``n_devices`` host
    devices (existing XLA_FLAGS are preserved, any prior force-count flag
    is replaced).  The child is held to the CPU backend
    (``JAX_PLATFORMS=cpu``): on a machine with an accelerator it must
    never reach for a chip that its parent may hold."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(FORCE_FLAG)]
    flags.append(f"{FORCE_FLAG}={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    src = os.path.join(_repo_root(), "src")
    pp = env.get("PYTHONPATH", "")
    if src not in pp.split(os.pathsep):
        env["PYTHONPATH"] = src + (os.pathsep + pp if pp else "")
    return env


def ensure_host_devices(n_devices: int = DEFAULT_DEVICES) -> None:
    """Make this process see ``n_devices`` host devices.

    Must run before jax initializes its backend; raises with instructions
    when it is already too late.
    """
    if "jax" in sys.modules:
        initialized = True
        try:
            from jax._src import xla_bridge
            initialized = xla_bridge.backends_are_initialized()
        except Exception:  # noqa: BLE001 — private API moved: assume locked
            pass
        if initialized:
            import jax
            have = len(jax.devices())
            if have < n_devices:
                raise RuntimeError(
                    f"jax already initialized with {have} device(s); set "
                    f"XLA_FLAGS={FORCE_FLAG}={n_devices} before importing "
                    f"jax (or use runtime.harness.run_subprocess)")
            return
        # imported but backend not created yet: XLA_FLAGS still applies
    os.environ["XLA_FLAGS"] = host_device_env(n_devices)["XLA_FLAGS"]


def run_subprocess(source: str, n_devices: int = DEFAULT_DEVICES,
                   timeout: float = 560.0,
                   extra_args: list[str] | None = None
                   ) -> subprocess.CompletedProcess:
    """Run ``python -c source`` (or ``python -m source`` when it names a
    dotted module path) with ``n_devices`` forced host devices and src on
    PYTHONPATH."""
    if re.fullmatch(r"[A-Za-z_][\w]*(\.[A-Za-z_][\w]*)*", source):
        cmd = [sys.executable, "-m", source]
    else:
        cmd = [sys.executable, "-c", source]
    return subprocess.run(cmd + (extra_args or []), capture_output=True,
                          text=True, env=host_device_env(n_devices),
                          timeout=timeout, cwd=_repo_root())
