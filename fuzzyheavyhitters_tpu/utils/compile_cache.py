"""Persistent XLA compilation cache wiring — the ONE place that places it.

Compile churn is a first-order cost of the crawl: every new frontier
bucket size (1 -> 2 -> 4 ... as sites' prefixes separate) recompiles the
expand / GC / OT programs, and a cold compile of the whole ladder is
minutes of wall-clock billed into whatever happens to run first.  JAX
ships a persistent on-disk compilation cache keyed by the HLO
fingerprint; a stable directory makes every *repeat* compile (a second
bench section, a restarted server, the next run) a cache read instead.
The directory is part of the key, so it must not move between runs.

Placement comes from outside: when ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX's own handling of that variable stands and this module sets no
directory; otherwise the cache lives at ``<checkout>/.jax_cache`` (the
directory above this package, git-ignored) — the same path on every
run.  ``enable()`` additionally drops the two thresholds that would
otherwise skip small/fast programs, and returns the directory in use —
or ``None`` (with a ``compile_cache.unavailable`` warning) when the
fallback directory cannot be created; the crawl then recompiles cold.
``chip_smoke.py`` treats ``None`` as a failure.  The binaries
(bin/leader, bin/server), benchmark/run.py, chip_smoke.py and
tests/conftest.py all call it at startup.

:func:`backend_compiles` counts fresh XLA backend compiles process-wide
(via jax.monitoring) — the acceptance instrument for warmup coverage:
a crawl over warmed shapes must add ZERO to it.  Under the installed JAX
the event also fires when the PERSISTENT cache serves the program (PR 25's
warm chip run counted as many as its cold one); only the in-process
executable cache keeps the count still, and
:func:`backend_compile_seconds` shows what a persistent hit saved.
"""

from __future__ import annotations

import os
import threading

_enabled: str | None = None

# fresh-compile accounting: every backend compile (an XLA compile that
# was NOT served from any cache — the thing warmup exists to take off
# the measured clock) bumps this counter via jax.monitoring.  Tests pin
# the warmed-crawl contract with it: a crawl on warmed shapes must
# report a delta of ZERO (a per-batch static arg, a fresh jit wrapper
# per call, or a warmup coverage hole all break that loudly).
_compile_lock = threading.Lock()
_compile_count = 0  # fhh-guard: _compile_count=_compile_lock
_compile_seconds = 0.0  # fhh-guard: _compile_seconds=_compile_lock
_listener_on = False  # fhh-guard: _listener_on=_compile_lock

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event_duration(name: str, duration: float = 0.0, *_a, **_k) -> None:
    global _compile_count, _compile_seconds
    if name == _COMPILE_EVENT:
        with _compile_lock:
            _compile_count += 1
            _compile_seconds += duration


def _listen() -> None:
    """Register the one jax.monitoring listener on first use (it stays
    for the process lifetime — listeners cannot unregister portably)."""
    global _listener_on
    with _compile_lock:
        if not _listener_on:
            import jax

            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration
            )
            _listener_on = True


def backend_compiles() -> int:
    """Process-wide count of fresh XLA backend compiles so far; snapshot
    before and after the measured region and compare deltas."""
    _listen()
    with _compile_lock:
        return _compile_count


def backend_compile_seconds() -> float:
    """Seconds those compiles took, process-wide (the same events'
    durations) — what chip_smoke.py takes out of a phase's wall clock."""
    _listen()
    with _compile_lock:
        return _compile_seconds


_suspended: str | None = None  # fhh-guard: _suspended=_compile_lock


def suspend(reason: str) -> None:
    """Stop THIS process reading and writing the persistent cache from
    here on: what it compiled or loaded so far stays in the in-process
    executable cache, everything new compiles fresh.  For a process
    that is about to run programs whose cached form does not survive
    (``parallel.server_mesh.ServerMesh``: a multi-chip program on a
    device set that does not start at the first local chip halts the
    TPU when its executable comes back from the cache).  Idempotent;
    :func:`enable` keeps returning the directory it established."""
    global _suspended
    with _compile_lock:
        if _suspended is not None:
            return
        _suspended = reason
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from .. import obs

    jax.config.update("jax_enable_compilation_cache", False)
    # the cache's "is it in use" answer is memoised at the first compile
    compilation_cache.reset_cache()
    obs.emit("compile_cache.suspended", severity="warn", reason=reason)


# <checkout>/.jax_cache: beside the package, wherever the checkout sits
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str | None:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` as JAX itself read it when
    set (no directory is set here), else :data:`DEFAULT_DIR`.  ``None``
    when that fallback directory cannot be created (logged as a
    warning).  Idempotent — later calls return the established path."""
    global _enabled
    if _enabled is not None:
        return _enabled
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            from .. import obs

            obs.emit(
                "compile_cache.unavailable",
                severity="warn",
                path=path,
                error=f"{type(e).__name__}: {e}",
            )
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    # default thresholds skip sub-second / sub-MB programs — exactly
    # the per-bucket expand/GC kernels whose churn this exists to kill
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled = path
    return path
