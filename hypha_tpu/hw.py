"""Device policy: which backend counts as the chip, and where compiled
programs are cached.

The framework asks one question — "is the active JAX backend the TPU?" — to
pick the pallas flash kernel over the XLA dense path. The chip path never
interprets a kernel: code that chose a kernel because of the platform passes
``interpret=False``, so a process on the wrong backend fails instead of
running the interpreter.

Reference seam: the reference picks its compute device via torch/Accelerate
device strings (``executors/accelerate/src/hypha/accelerate_executor/
training.py``); this is the TPU-native equivalent of that selection.
"""

from __future__ import annotations

import os
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent


def is_accelerator() -> bool:
    """True when the active JAX backend is the TPU, the only backend that
    lowers the pallas-TPU kernels (pltpu VMEM scratch etc.). Everything
    else takes the XLA dense path."""
    import jax

    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Pallas interpret mode for kernels called with ``interpret=None`` —
    for CPU tests only. Entry points that run on the chip (the train
    executor, ``chip_smoke.py``) pass ``interpret=False``."""
    return not is_accelerator()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Every entry point that will touch JAX calls this once before its first
    compile. ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside and
    is then left alone (jax reads it itself); unset, the cache lives at the
    fixed path ``<checkout>/.jax_cache`` — a directory that moves never
    hits, so it is never a temp name, pid or time."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = str(_REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
