"""What the platform decides, decided in ONE place: which implementation
an op runs, whether the serving path may start, where compiled programs
are cached.

The repo runs on two platforms: ``tpu`` (production — every Pallas kernel
compiles through Mosaic) and ``cpu`` (tests — the XLA reference, or the
Pallas interpreter when a test asks for it). Anything else is an error,
never a quiet drop to interpret mode or a gather reference: a run that
looks like a chip run but is not is worse than one that stops.
"""

from __future__ import annotations

import os

import jax

# the persistent compile cache when the environment names none: a fixed
# path inside the checkout — the directory is part of the cache key, so
# one built from a temp name, pid or time never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

COMPILED = "compiled"    # pallas_call lowered by Mosaic
INTERPRET = "interpret"  # pallas_call under the Pallas interpreter
REFERENCE = "reference"  # the op's pure-XLA reference


def kernel_mode(interpret: bool | None = None) -> str:
    """How a Pallas-backed op runs on the current default backend.

    ``interpret`` is the caller's explicit request and wins on both
    platforms (``True`` → interpreter, ``False`` → Mosaic); ``None``
    selects by platform: ``tpu`` → compiled kernel, ``cpu`` → reference.
    Any other platform raises."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"gofr_tpu ops support the tpu and cpu platforms; jax's default "
            f"backend is {platform!r}"
        )
    if interpret is not None:
        return INTERPRET if interpret else COMPILED
    return COMPILED if platform == "tpu" else REFERENCE


def require_requested_backend() -> str:
    """The serving path's platform guard: return the default backend's
    platform, raising unless it is ``tpu`` or a platform somebody named
    (``JAX_PLATFORMS`` / ``jax.config.jax_platforms``). jax with libtpu
    installed and no chip otherwise drops to CPU with a warning, and a
    server would serve from it."""
    platform = jax.default_backend()
    requested = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS") or ""
    if platform != "tpu" and platform not in requested.split(","):
        raise RuntimeError(
            f"no TPU: jax's default backend is {platform!r} and nobody asked "
            f"for it (JAX_PLATFORMS={requested!r}); set JAX_PLATFORMS (e.g. "
            "JAX_PLATFORMS=cpu for tests) to serve from it on purpose"
        )
    return platform


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns the directory.
    ``JAX_COMPILATION_CACHE_DIR`` set → jax reads it itself and no code
    sets a directory; unset → :data:`COMPILE_CACHE_DIR`. Called where the
    serving path starts (before its first compile) and is idempotent."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
