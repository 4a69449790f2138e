"""XLA backend configuration: platform select, fake devices, overlap flags,
and the persistent compilation cache.

One place for the process-level knobs every launcher otherwise hand-rolls
(the bayespec ``config.py`` pattern): pick the platform, fake a multi-device
host mesh on CPU, turn on the XLA flags that let collectives overlap with
compute on GPU, and place the compile cache.  The env-var plumbing must land
BEFORE the jax backend initializes (first ``jax.devices()``/computation) —
flags set later do not take effect — so this module imports jax lazily:
importing it is always safe, even ahead of the env setup it performs.

Typical launcher prologue::

    from repro.dist import backend
    backend.configure(fake_devices=os.environ.get("REPRO_FAKE_DEVICES"))
    backend.enable_compile_cache()
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

# Latency-hiding flags for GPU meshes: run collectives on their own async
# stream and let the scheduler overlap them with compute — the decode-loop
# hot path (scale hot-swaps + logitshard max-reduce) is collective-bound
# without them.  Harmless to set on CPU/TPU (XLA ignores unknown-backend
# flags at CPU backend init).
GPU_OVERLAP_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

PLATFORMS = ("cpu", "gpu", "tpu")


# the repository checkout this module lives in (src/repro/dist/backend.py)
CHECKOUT = Path(__file__).resolve().parents[3]


def _append_xla_flags(*flags: str) -> None:
    """Append ``flags`` to ``XLA_FLAGS``, skipping ones already present."""
    current = os.environ.get("XLA_FLAGS", "")
    fresh = [f for f in flags if f.split("=")[0] not in current]
    if not fresh:
        return
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [current, *fresh]))


def set_platform(platform: str) -> None:
    """Pin the jax platform (``cpu``/``gpu``/``tpu``) for this process."""
    if platform not in PLATFORMS:
        raise ValueError(f"unknown platform {platform!r} "
                         f"(know: {', '.join(PLATFORMS)})")
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def fake_host_devices(n: int) -> None:
    """Split the host CPU into ``n`` XLA devices (CI mesh emulation).

    Must run before the CPU backend initializes; no-op if ``XLA_FLAGS``
    already pins a device count (launchers may pre-set it before import).
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"fake device count {n} must be >= 1")
    _append_xla_flags(f"--xla_force_host_platform_device_count={n}")


def enable_gpu_overlap() -> None:
    """Turn on async-collective + latency-hiding scheduling for GPU."""
    _append_xla_flags(*GPU_OVERLAP_FLAGS)


def configure(*, platform: Optional[str] = None,
              fake_devices: Optional[int] = None,
              gpu_overlap: Optional[bool] = None) -> None:
    """One-stop launcher prologue.  Every argument is optional:

    * ``platform`` — pin ``JAX_PLATFORMS``.
    * ``fake_devices`` — fake-device count (e.g. from REPRO_FAKE_DEVICES).
    * ``gpu_overlap`` — GPU latency-hiding flags; defaults to on exactly
      when ``platform == "gpu"``.
    """
    if platform is not None:
        set_platform(platform)
    if fake_devices:
        fake_host_devices(int(fake_devices))
    if gpu_overlap if gpu_overlap is not None else platform == "gpu":
        enable_gpu_overlap()


def enable_compile_cache() -> str:
    """Keep compiled programs in a persistent cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here overrides it.  Otherwise the cache is ``<checkout>/.jax_cache``
    — a fixed path, since the cache key depends on it.  Call before the
    first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def summary() -> Dict:
    """What this process actually got (initializes the backend)."""
    import jax

    return {"platform": jax.default_backend(),
            "device_count": jax.device_count(),
            "local_device_count": jax.local_device_count(),
            "xla_flags": os.environ.get("XLA_FLAGS", "")}
