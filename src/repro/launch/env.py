"""Process environment/config layer — applied BEFORE jax imports.

jax freezes its backend the first time it initializes, and XLA reads its
flags from the environment at that moment: platform selection, x64 mode,
host device count, and the GPU latency-hiding/async-collective flags are
all silently ignored if set after ``import jax`` has run its course. This
module owns that footgun in ONE place (the bayespec ``config.py`` pattern,
SNIPPETS.md §1): launchers and benchmarks call ``apply`` (or
``apply_from_environ``) at the very top of the file, before any import
that pulls jax in.

This module is deliberately stdlib-only — importing it never initializes
any backend.

Environment variables understood by ``apply_from_environ`` (all optional;
explicit ``EnvConfig`` fields win over them):

  * ``REPRO_PLATFORM``      -> ``JAX_PLATFORMS`` (cpu/gpu/tpu)
  * ``REPRO_X64``           -> ``JAX_ENABLE_X64`` (1/true/0/false)
  * ``REPRO_HOST_DEVICES``  -> ``--xla_force_host_platform_device_count``
  * ``REPRO_TILE_TABLE``    -> consumed by ``repro.kernels.autotune``
    directly; listed here because this layer is where deployments set it.
  * ``JAX_COMPILATION_CACHE_DIR`` -> JAX's persistent compilation cache.
    Left as it is when set; otherwise ``apply_from_environ`` points it at
    ``<checkout>/.jax_cache``. The path is part of the cache's key, so it
    is fixed: every process of one checkout finds what an earlier one
    compiled.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import warnings
from typing import Dict, Optional, Tuple

# GPU flags from the bayespec exemplar: overlap collective communication
# with compute (latency-hiding scheduler + async collectives). Harmless
# no-ops for XLA:CPU/TPU — they are only read by the GPU backend.
GPU_XLA_FLAGS: Tuple[str, ...] = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_async_collectives=true",
)

_TRUTHY = ("1", "true", "yes", "on")

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/env.py -> the checkout root.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


@dataclasses.dataclass
class EnvConfig:
    """What to pin before backend init; None fields are left untouched."""

    platform: Optional[str] = None       # "cpu" | "gpu" | "tpu"
    enable_x64: Optional[bool] = None    # float64/int64 as default widths
    host_devices: Optional[int] = None   # fake host devices (shard tests);
    #                                      0/None = leave XLA_FLAGS alone
    gpu_flags: bool = False              # append GPU_XLA_FLAGS
    preallocate_gpu: Optional[bool] = None  # XLA client memory strategy
    extra_xla_flags: Tuple[str, ...] = ()


def _merge_xla_flags(existing: str, new_flags: Tuple[str, ...]) -> str:
    """Append flags not already present (by --flag-name prefix), so a
    user's explicit setting always wins over ours."""
    parts = existing.split()
    have = {p.split("=", 1)[0] for p in parts}
    for flag in new_flags:
        if flag.split("=", 1)[0] not in have:
            parts.append(flag)
    return " ".join(parts)


def apply(cfg: EnvConfig) -> Dict[str, str]:
    """Pin ``cfg`` into ``os.environ``; returns the variables written.

    Warns (rather than raises) when jax is already imported — the
    settings may or may not stick at that point, and the caller should
    move the ``apply`` above its jax-importing imports.
    """
    if "jax" in sys.modules:
        warnings.warn(
            "repro.launch.env.apply() called AFTER jax was imported - "
            "backend/platform/x64/XLA flags may be ignored. Call it at "
            "the top of the launcher, before jax-importing imports.",
            RuntimeWarning, stacklevel=2)
    written: Dict[str, str] = {}
    if cfg.platform is not None:
        written["JAX_PLATFORMS"] = cfg.platform
    if cfg.enable_x64 is not None:
        written["JAX_ENABLE_X64"] = "1" if cfg.enable_x64 else "0"
    if cfg.preallocate_gpu is not None:
        written["XLA_PYTHON_CLIENT_PREALLOCATE"] = \
            "true" if cfg.preallocate_gpu else "false"
    xla_new: Tuple[str, ...] = ()
    if cfg.host_devices:
        xla_new += (
            f"--xla_force_host_platform_device_count={cfg.host_devices}",)
    if cfg.gpu_flags:
        xla_new += GPU_XLA_FLAGS
    xla_new += tuple(cfg.extra_xla_flags)
    if xla_new:
        written["XLA_FLAGS"] = _merge_xla_flags(
            os.environ.get("XLA_FLAGS", ""), xla_new)
    os.environ.update(written)
    return written


def apply_from_environ() -> Dict[str, str]:
    """``apply`` driven purely by ``REPRO_*`` variables — the one-liner
    for launchers whose argparse runs after jax-importing imports. Also
    places JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``
    stays as set, or becomes ``DEFAULT_CACHE_DIR`` when unset."""
    cfg = EnvConfig()
    if os.environ.get("REPRO_PLATFORM"):
        cfg.platform = os.environ["REPRO_PLATFORM"]
    if "REPRO_X64" in os.environ:
        cfg.enable_x64 = os.environ["REPRO_X64"].lower() in _TRUTHY
    if os.environ.get("REPRO_HOST_DEVICES"):
        cfg.host_devices = int(os.environ["REPRO_HOST_DEVICES"])
    written = apply(cfg)
    if not os.environ.get(CACHE_ENV_VAR):
        os.environ[CACHE_ENV_VAR] = written[CACHE_ENV_VAR] = \
            DEFAULT_CACHE_DIR
    return written


__all__ = ["CACHE_ENV_VAR", "DEFAULT_CACHE_DIR", "EnvConfig", "GPU_XLA_FLAGS",
           "apply", "apply_from_environ"]
