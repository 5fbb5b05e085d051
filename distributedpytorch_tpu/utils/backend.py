"""Which device this process runs on, decided in one place.

jax falls back to the CPU without a word when it finds no TPU and
``JAX_PLATFORMS`` is unset. The rule for this program, with no knob of
its own: **the CPU is used only where the operator named it**
(``JAX_PLATFORMS=cpu``, as the tests and the virtual-mesh tools do).
Three things follow from that one rule and live here:

* :func:`require_accelerator` — every entry point (``train.py``,
  ``serve``, the tools) calls it before it builds anything: a TPU is
  fine, an operator-named CPU is fine, anything else exits non-zero.
* :func:`pallas_interpret` — the Pallas tier's one interpret/Mosaic
  decision: Mosaic on a TPU, the interpreter only on an operator-named
  CPU, an error otherwise. A requested kernel is never silently
  interpreted.
* :func:`enable_compilation_cache` — JAX's persistent compile cache,
  placed from outside by ``JAX_COMPILATION_CACHE_DIR`` or, unset, at one
  fixed git-ignored directory in the checkout. The directory is part of
  the cache key, so it is never built from a temp name, pid or time.
"""

from __future__ import annotations

import os

import jax

#: The in-checkout cache directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is unset (listed in ``.gitignore``).
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, ".jax_cache"
))


def names_cpu(jax_platforms: str) -> bool:
    """Whether a ``JAX_PLATFORMS`` value names the CPU first."""
    return (jax_platforms or "").split(",")[0].strip().lower() == "cpu"


def operator_named_cpu() -> bool:
    """True when ``JAX_PLATFORMS`` (env or ``jax.config``) names the CPU
    first — the only way this program ends up on the CPU on purpose."""
    return names_cpu(
        jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    )


def require_accelerator(entry: str) -> str:
    """The backend ``entry`` will run on: ``"tpu"``, or ``"cpu"`` where
    the operator named it. Any other outcome — jax found no TPU and
    quietly picked the CPU, or picked a GPU this program has no kernels
    for — ends the process with a non-zero exit code."""
    backend = jax.default_backend()
    if backend == "tpu" or (backend == "cpu" and operator_named_cpu()):
        return backend
    raise SystemExit(
        f"{entry}: jax selected the {backend!r} backend but no TPU — "
        "refusing to carry on. Run on a machine with a TPU, or name "
        "the CPU yourself with JAX_PLATFORMS=cpu (tests, virtual-mesh "
        "drills)."
    )


def pallas_interpret() -> bool:
    """``interpret=`` for every ``pallas_call`` of the package whose
    caller passed ``None``: Mosaic on a TPU, the interpreter on an
    operator-named CPU, an error anywhere else."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if platform == "cpu" and operator_named_cpu():
        return True
    raise RuntimeError(
        f"a Pallas kernel was requested on {platform!r}, which is "
        "neither a TPU nor a CPU named with JAX_PLATFORMS=cpu — it will "
        "not be run in the interpreter behind your back"
    )


def device_memory_bytes():
    """What the first local device reports as its memory
    (``memory_stats()["bytes_limit"]``: 16.9 GB on a v5e chip), or
    ``None`` where it reports none, as the CPU."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it,
    and code sets no other. Unset: :data:`DEFAULT_CACHE_DIR`."""
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        return external
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
