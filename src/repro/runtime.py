"""Process-level runtime policy: the compile cache and the chip's owner.

Two rules that every entry point shares:

  * **Compile cache.** `enable_compile_cache()` points JAX's persistent
    compilation cache at a fixed directory, so repeated runs of an entry
    point reuse compiled kernels.  Where `JAX_COMPILATION_CACHE_DIR` is
    set, JAX reads it itself and nothing is set here; otherwise the cache
    lives at `<checkout>/.jax_cache` (git-ignored).  Only the entry points
    call it (the `__main__`s and `chip_smoke.py`), never an import.
  * **One process per chip.** A TPU belongs to the process that first
    touched it; a child process that needs it then fails or hangs.
    `refuse_device_children` is called wherever the code would spawn
    children that run a device backend, and on a TPU it raises with the
    in-process alternative.  Children that only do host work call
    `pin_to_cpu` first thing, in their own environment.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on the persistent compile cache for this process (entry
    points only); returns its directory: the env var, else the default."""
    import jax

    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def refuse_device_children(what: str, alternative: str) -> None:
    """Raise on a TPU: `what` would start children that need the chip."""
    if on_tpu():
        raise RuntimeError(
            f"{what} would start child processes that need the TPU, which "
            f"this process holds (a chip belongs to one process); use "
            f"{alternative} instead")


def pin_to_cpu() -> None:
    """Keep this child process off the accelerator (host work only)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")
