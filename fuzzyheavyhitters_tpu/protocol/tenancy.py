"""Tenant scheduling: interleave device work across collection sessions.

The ``pipeline_stalls`` telemetry (PR 5) names the idle device gaps a
single collection leaves: while one span's GC/OT exchange is on the
wire, the device sits idle.  With per-collection sessions
(protocol/sessions.py) a SECOND tenant's expand/kernel stage can fill
exactly those gaps — each session serializes its own verbs on its own
lock, so two sessions' verbs already interleave on the event loop; this
module makes that interleaving *scheduled* (FIFO-fair device turns) and
*observable* (stall-fill accounting):

- :class:`TenantScheduler` — ``device_turn(key)`` brackets a session's
  device-dispatch stage (one accelerator: turns serialize FIFO across
  sessions, so a tenant's dispatch burst cannot starve another's
  indefinitely — asyncio.Lock wakes waiters in acquisition order);
  ``wire_wait(key)`` brackets a session's data-plane waits.  A device
  turn taken while ANOTHER session is wire-waiting is a **stall fill**:
  the multi-tenant win, counted per server (``tenant_stall_fills`` /
  ``tenant_device_turns``) and surfaced through ``status``, the run
  report, and ``bench_multitenant``.
- :class:`WarmLadder` — the process-level registry of already-warmed
  compiled-program shapes.  jit executables are cached per process, so
  once ANY session warmed a (batch, bucket, path, layout) rung, a new
  collection on the same shape pays zero fresh compiles — the ladder
  makes warmup itself skip the redundant execution (warming runs real
  device programs; re-running them per tenant would cost seconds per
  rung for nothing).
"""

from __future__ import annotations

import asyncio
import contextlib
import time

from ..obs import trace as obstrace


class TenantScheduler:
    """FIFO device-turn scheduler + stall-fill accounting (module doc).

    All state mutates from the owning server's event loop only — the
    counters need no lock; the ``obs`` registry has its own."""

    def __init__(self, obs=None):
        self.obs = obs
        self._device_lock = asyncio.Lock()
        # session key -> depth of active wire waits (a session can hold
        # at most one at a time under its verb lock, but recovery paths
        # may nest; a count is the safe shape)
        self._wire: dict[str, int] = {}
        self.device_turns = 0
        self.stall_fills = 0
        self.turns_by_session: dict[str, int] = {}
        self.fills_by_session: dict[str, int] = {}
        # session key -> wall-clock of its last device turn: the age of
        # the OLDEST entry is the "is anything starving here" placement
        # signal the fleet layer reads (FleetDirectory.note_load)
        self.last_progress_s: dict[str, float] = {}

    # -- accounting primitives --------------------------------------------

    def _others_on_wire(self, key: str) -> bool:
        return any(n > 0 and k != key for k, n in self._wire.items())

    def _note_turn(self, key: str) -> None:
        self.device_turns += 1
        self.turns_by_session[key] = self.turns_by_session.get(key, 0) + 1
        self.last_progress_s[key] = time.time()
        if self.obs is not None:
            self.obs.count("tenant_device_turns")
        if self._others_on_wire(key):
            self.stall_fills += 1
            self.fills_by_session[key] = (
                self.fills_by_session.get(key, 0) + 1
            )
            if self.obs is not None:
                self.obs.count("tenant_stall_fills")

    # -- public API --------------------------------------------------------

    def device_turn(self, key: str, count: bool = True):
        """Async context manager bracketing one session's device-dispatch
        stage.  Turns serialize FIFO across sessions (one accelerator);
        acquiring while another session waits on the wire counts a
        stall fill.  ``count=False`` keeps the serialization but skips
        the accounting — the caller's dispatch already ran (and was
        counted) at frame arrival via :meth:`note_dispatch`, and
        double-counting would inflate the fill-ratio denominator."""
        return _DeviceTurn(self, key, count)

    @contextlib.contextmanager
    def wire_wait(self, key: str):
        """Sync context manager marking a session as blocked on the
        data plane (wraps the recv awaits in protocol/rpc.py, inside the
        session registry's ``wire_wait`` span): the gap a second
        tenant's device turn fills."""
        self._wire[key] = self._wire.get(key, 0) + 1
        try:
            yield
        finally:
            n = self._wire.get(key, 1) - 1
            if n <= 0:
                self._wire.pop(key, None)
            else:
                self._wire[key] = n

    def note_dispatch(self, key: str) -> None:
        """Lock-free turn accounting for dispatch sites that cannot
        await (the frame-arrival pre-expand runs outside any lock and
        must stay event-loop-atomic)."""
        self._note_turn(key)

    def wire_waiting(self) -> list:
        return sorted(k for k, n in self._wire.items() if n > 0)

    def forget(self, key: str) -> None:
        """Drop one session's accounting rows (retire / migration away):
        a dead tenant must not hold the pair's progress-age signal high
        forever."""
        self.turns_by_session.pop(key, None)
        self.fills_by_session.pop(key, None)
        self.last_progress_s.pop(key, None)

    def fleet_load(self, now: float | None = None) -> dict:
        """The pair-half's placement signals, in exactly the shape
        :meth:`FleetDirectory.note_load` consumes: the stall-fill ratio
        (how contended this accelerator is) and the age of the
        least-recently-progressing session (is anything starving)."""
        if now is None:
            now = time.time()
        ages = [now - t for t in self.last_progress_s.values()]
        return {
            "stall_fill_ratio": round(
                self.stall_fills / max(1, self.device_turns), 6
            ),
            "max_progress_age_s": round(max(ages, default=0.0), 3),
        }

    def stats(self) -> dict:
        return {
            "device_turns": self.device_turns,
            "stall_fills": self.stall_fills,
            "fill_ratio": round(
                self.stall_fills / max(1, self.device_turns), 6
            ),
            "turns_by_session": dict(sorted(self.turns_by_session.items())),
            "fills_by_session": dict(sorted(self.fills_by_session.items())),
            "wire_waiting": self.wire_waiting(),
        }


class _DeviceTurn:
    __slots__ = ("_sched", "_key", "_count", "_trace")

    def __init__(self, sched: TenantScheduler, key: str, count: bool = True):
        self._sched = sched
        self._key = key
        self._count = count
        self._trace = None

    async def __aenter__(self):
        # the span covers lock wait + dispatch: a long device_turn with
        # a short dispatch IS the cross-tenant queueing the scheduler
        # exists to make visible
        if obstrace.enabled():
            obs = self._sched.obs
            self._trace = obstrace.span_begin(
                "device_turn", obs.name if obs is not None else "server"
            )
        await self._sched._device_lock.acquire()
        if self._count:
            self._sched._note_turn(self._key)
        return self

    async def __aexit__(self, exc_type, exc, tb):
        self._sched._device_lock.release()
        if self._trace is not None:
            obstrace.span_end(self._trace, error=exc_type is not None)
        return False


# ---------------------------------------------------------------------------
# Warm ladder: process-level warmed-shape registry
# ---------------------------------------------------------------------------

# keys are tuples built by rpc._warm_bucket from everything that feeds a
# compiled program's identity (batch shapes, bucket, field ladder, ot
# path, engine layout, mesh/kernel shard plan).  Process-level on
# purpose: the jit executable cache is process-level, so two sessions —
# or two in-process servers, as in the bench and the tests — genuinely
# share the compiled programs the ladder tracks.
_WARMED: set = set()  # fhh-guard: _WARMED=_WARM_LOCK


# single event loop in production, but tests may probe from threads;
# a plain mutex keeps the set consistent either way
import threading as _threading  # noqa: E402

_WARM_LOCK = _threading.Lock()


def warmed(key: tuple) -> bool:
    """True when some session in this process already warmed ``key``
    (its compiled programs are in the process jit cache)."""
    with _WARM_LOCK:
        return key in _WARMED


def mark_warmed(key: tuple) -> None:
    with _WARM_LOCK:
        _WARMED.add(key)


def ladder_size() -> int:
    with _WARM_LOCK:
        return len(_WARMED)


def ladder_reset() -> None:
    """Test hook: forget every warmed shape (does NOT clear the jit
    cache — only the skip bookkeeping)."""
    with _WARM_LOCK:
        _WARMED.clear()
