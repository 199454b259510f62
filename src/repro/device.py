"""Which device the jax paths run on, and where compiled programs are kept.

Two platforms are supported and nothing in between:

- a TPU, where the Pallas kernels run compiled;
- the CPU, held there on purpose with ``JAX_PLATFORMS=cpu``, where the
  kernels run in Pallas interpret mode (the test path).

Any other outcome — in particular a CPU that JAX fell back to because the
accelerator failed to initialise — raises, so a run meant for the chip
cannot finish on the host and look as if it had used the chip.

This module imports jax only inside the functions that need it: numpy-only
processes (the dispatch tier's workers) ask :func:`held_to_cpu` without
paying the import.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, so every run of every entry point
# finds what the earlier ones compiled (the path is part of the key)
REPO_ROOT = Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"


def held_to_cpu() -> bool:
    """True when JAX is held to the CPU (``JAX_PLATFORMS=cpu``)."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


def default_interpret() -> bool:
    """Pallas interpret mode for the current platform: False on a TPU,
    True on a CPU that JAX was held to, an error anywhere else."""
    import jax
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu" and held_to_cpu():
        return True
    raise RuntimeError(
        f"jax found platform {platform!r} "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}): the "
        "Pallas kernels run compiled on a TPU, or in interpret mode when "
        "JAX is held to the CPU with JAX_PLATFORMS=cpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and is
    left alone; otherwise the cache lives in ``<checkout>/.jax_cache``.
    Every compile is kept, however short: the engine's kernels compile
    in well under JAX's default one-second threshold. Entry points call
    this; tests do not."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
