"""Device-memory and XLA-compile telemetry: the flagship-run risk gauges.

The 1M-client flagship plan (ROADMAP) carries two known physical risks
that were only visible post-mortem before this module: HBM residency
("1.51 chips of key storage") and recompile storms past the warmup
ladder.  Both become live, named numbers here:

- :func:`sample` reads per-device memory stats (``device.memory_stats()``
  — TPU/GPU runtimes report ``bytes_in_use``/``bytes_limit``) and sets
  ``hbm_in_use_bytes`` / ``hbm_watermark_bytes`` / ``hbm_delta_bytes``
  (and ``hbm_limit_bytes`` when the runtime knows its capacity) on a
  registry.  XLA:CPU reports no memory stats, so the fallback sums
  ``jax.live_arrays()`` — process-wide tracked-array bytes, the honest
  CPU analogue.  A ``phase`` argument adds a per-phase watermark
  (``hbm_watermark_bytes:<phase>`` — the colon becomes a ``key`` label
  at export).
- :func:`tree_nbytes` sizes a pytree of arrays; the session layer uses
  it to publish ``key_plane_bytes`` per collection where the key planes
  are allocated (sessions.add_key_batch; sessions.concat_keys for batches
  that arrive with no total).
- :func:`install_compile_listener` hooks JAX's monitoring event
  ``/jax/core/compile/backend_compile_duration`` (fires once per FRESH
  backend compile — persistent-cache hits do not fire it).  The event
  carries no program name, so each compile is attributed to the
  innermost active obs span (the phase breakdown IS our program naming:
  ``level``/``warmup``/``setup``/...), counted as ``fresh_compiles`` +
  ``fresh_compiles:<span>`` on the default registry.  After
  :func:`note_warmup_done` (the warmup verb's last act), compiles also
  count into ``fresh_compiles_post_warmup`` — the named, counted event
  the ``recompile_after_warmup`` alert rule watches.

``jax`` is imported lazily inside each function: the obs package stays
importable (and the exporter/alert plane usable) in jax-free tooling
contexts.
"""

from __future__ import annotations

import threading

from . import logs
from .metrics import Registry, all_registries, default_registry

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
# fhh-guard: _state=_lock
_state = {"listener": False, "warmup_done": False}


def device_bytes() -> tuple[int, int | None]:
    """(bytes in use, capacity or None) summed over local devices.
    Runtimes without memory stats (XLA:CPU) fall back to live-array
    bytes with an unknown capacity."""
    import jax

    in_use, limit, got = 0, 0, False
    for d in jax.local_devices():
        try:
            ms = d.memory_stats()
        # fhh-lint: disable=broad-except (telemetry probe: a backend
        # without the stats API must degrade to the fallback, not crash)
        except Exception:
            ms = None
        if ms and "bytes_in_use" in ms:
            got = True
            in_use += int(ms["bytes_in_use"])
            limit += int(ms.get("bytes_limit", 0))
    if got:
        return in_use, (limit or None)
    return live_array_bytes(), None


def live_array_bytes() -> int:
    """Process-wide bytes of live tracked jax arrays (the CPU fallback)."""
    import jax

    return int(sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays()))


def tree_nbytes(tree) -> int:
    """Total bytes of the array leaves of a pytree (0 for None)."""
    if tree is None:
        return 0
    import jax

    return int(
        sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree.leaves(tree)
        )
    )


def sample(reg: Registry | None = None, phase: str | None = None) -> int:
    """One memory sample onto ``reg`` (default registry when None):
    sets the in-use gauge, advances the watermark, records the delta
    since the previous sample, and (with ``phase``) a per-phase
    watermark.  Returns bytes in use."""
    reg = reg if reg is not None else default_registry()
    in_use, limit = device_bytes()
    prev = reg.gauge_value("hbm_in_use_bytes") or 0
    reg.gauge("hbm_in_use_bytes", in_use)
    reg.gauge("hbm_delta_bytes", in_use - prev)
    wm = reg.gauge_value("hbm_watermark_bytes") or 0
    if in_use > wm:
        reg.gauge("hbm_watermark_bytes", in_use)
    if limit:
        reg.gauge("hbm_limit_bytes", limit)
    if phase:
        key = f"hbm_watermark_bytes:{phase}"
        if in_use > (reg.gauge_value(key) or 0):
            reg.gauge(key, in_use)
    return in_use


# -- fresh-compile accounting ---------------------------------------------


def _span_name() -> str:
    """The innermost active span name across every live registry — the
    phase a compile is attributed to (``unknown`` outside any span)."""
    for reg in all_registries():
        sp = reg.current_span()
        if sp is not None:
            return sp.name
    return "unknown"


def _on_event(event: str, duration: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    reg = default_registry()
    name = _span_name()
    reg.count("fresh_compiles")
    reg.count(f"fresh_compiles:{name}")
    reg.timer_add("xla_compile", duration)
    with _lock:
        warm = _state["warmup_done"]
    if warm:
        reg.count("fresh_compiles_post_warmup")
        logs.emit(
            "compile.post_warmup", severity="debug",
            program=name, seconds=round(duration, 4),
        )


def install_compile_listener() -> None:
    """Register the per-compile listener once per process.  JAX offers
    no unregister, so this is a one-way, idempotent switch — same
    contract as utils.compile_cache.backend_compiles()."""
    with _lock:
        if _state["listener"]:
            return
        _state["listener"] = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event)


def note_warmup_done() -> None:
    """Mark the warmup ladder complete: every fresh compile after this
    is a ``fresh_compiles_post_warmup`` event (and alert fodder)."""
    with _lock:
        _state["warmup_done"] = True


def warmup_done() -> bool:
    with _lock:
        return _state["warmup_done"]


def _reset_for_tests() -> None:
    """Clear the warmup flag (the listener itself cannot unregister)."""
    with _lock:
        _state["warmup_done"] = False
