"""The device rule, the chip check and the compile-cache placement.

JAX selects the platform (``JAX_PLATFORMS``, else the best backend the
installation has); the program never switches it. An entry point that
needs the chip calls :func:`require_tpu` once and fails otherwise; every
record an entry point prints carries :func:`device_record`, so a number
can always be read against the device it ran on.

A chip belongs to one process: whoever calls ``jax.devices()`` first
holds it, and a child that needs it then fails or hangs. Nothing here
spawns a process.
"""

from __future__ import annotations

import os

#: the in-checkout compile cache (git-ignored), used when the environment
#: places none — a fixed path, because the path is part of the cache key
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_compile_cache")


def device_record() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them. Initialises the backend, i.e. takes the chip on a chip host."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_tpu() -> dict:
    """The chip check: :func:`device_record`, or RuntimeError when JAX's
    default backend is not a TPU. No fallback — a measurement path that
    finds no chip fails."""
    record = device_record()
    if record["platform"] != "tpu":
        raise RuntimeError(
            f"this entry point needs a TPU; JAX selected "
            f"{record['platform']!r} ({record['device_kind']}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    return record


def arm_compile_cache() -> str | None:
    """Place the persistent compilation cache; call before the first jit.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    no directory is set in code; where it is not, the cache lives at
    ``<checkout>/.jax_compile_cache``. Also arms the devprof cache
    hit/miss counters (``devprof.compile_totals()["cache"]``). Returns
    the directory in force — None on a CPU backend nobody placed a cache
    for: its compiles take seconds, and XLA:CPU logs an E-level
    ``cpu_aot_loader`` "machine type ... doesn't match" line (~3 KB) for
    every executable it reloads, on the very machine that compiled it
    (seen with ``sda-sim`` run twice on this installation, PR 22).

    Wherever a cache is left in force its key takes the ops' metadata
    in: the stage scopes (docs/observability.md) are the program's
    device-side spans and live in that metadata, which JAX's key leaves
    out by default, so an executable cached by a program with other
    scopes would be loaded with those scopes on every op of the trace.
    """
    import jax

    from ..obs import devprof

    devprof.install_monitoring()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        if jax.default_backend() == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return placed or _CHECKOUT_CACHE


def force_cpu(n_devices: int = 1) -> None:
    """The CPU dry run: a CPU backend with ``n_devices`` virtual devices,
    for mesh logic without chips (``dryrun_multichip``, the mesh tests).
    The one place besides ``tests/conftest.py`` and the ``--multihost``
    worker that sets the platform."""
    import jax
    from jax.extend.backend import clear_backends

    # a backend this process already initialised pins its device count
    clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    got = jax.local_device_count()
    if got < n_devices:
        raise RuntimeError(
            f"CPU backend came up with {got} devices, need {n_devices}")
