"""Retry/deadline policy: the shared vocabulary for every recovery loop.

The transport layer (protocol/rpc.py), the leader supervision
(protocol/leader_rpc.py), and the chaos tests all speak these types, so
"how long do we wait, how often do we retry, which failures are worth
retrying" is decided in ONE place instead of three fixed-sleep loops with
three hardcoded answers.

Design points:

- **Full jitter** (AWS architecture-blog style): the k-th delay is
  ``uniform(0, min(cap, base·factor^k))``.  Two leaders redialing the
  same restarted server must not reconnect in lockstep.
- **Deadlines compose with retries**: a :class:`Deadline` is a wall-clock
  budget shared across every attempt (dial + send + response), not a
  per-attempt timeout; :meth:`RetryPolicy.delays` stops yielding when the
  deadline cannot fit another attempt.
- **Classification is a default, not a straitjacket**: transient =
  transport-shaped (reset/EOF/refused/timeout/corrupt frame — exactly
  the set ``CollectorClient._read_loop`` treats as connection loss).
  Protocol errors (a server ``__error__`` response, a verb rejecting a
  request) are FATAL to the retry loop: replaying them cannot succeed
  and may not be idempotent-safe at a semantic level the dedup cache
  can't see.
"""

from __future__ import annotations

import asyncio
import pickle
import random
import time
from dataclasses import dataclass, field

from .. import obs

# transport-shaped failures: retrying/redialing has a chance of working.
# asyncio.IncompleteReadError subclasses EOFError; ConnectionError and
# TimeoutError both subclass OSError on 3.10+... except asyncio.TimeoutError
# which aliases TimeoutError from 3.11 only — list both explicitly.
TRANSIENT_ERRORS: tuple[type[BaseException], ...] = (
    ConnectionError,
    EOFError,  # covers asyncio.IncompleteReadError
    OSError,
    TimeoutError,
    asyncio.TimeoutError,
    pickle.UnpicklingError,  # torn/corrupt frame == transport loss
)


def is_transient(exc: BaseException) -> bool:
    """True when ``exc`` is worth a redial/replay.  Everything else is a
    bug or a protocol-level rejection: replaying it burns the budget and
    can mask real failures."""
    return isinstance(exc, TRANSIENT_ERRORS)


class Deadline:
    """A wall-clock budget anchored at construction.  ``budget_s=None``
    means unbounded (every query returns "plenty left")."""

    __slots__ = ("budget_s", "_t0")

    def __init__(self, budget_s: float | None):
        self.budget_s = budget_s
        self._t0 = time.monotonic()

    def elapsed(self) -> float:
        return time.monotonic() - self._t0

    def remaining(self) -> float | None:
        """Seconds left (clamped at 0.0), or None when unbounded."""
        if self.budget_s is None:
            return None
        return max(0.0, self.budget_s - self.elapsed())

    def expired(self) -> bool:
        rem = self.remaining()
        return rem is not None and rem <= 0.0

    async def wait_for(self, aw):
        """``asyncio.wait_for`` bounded by what's LEFT of this budget (not
        a fresh per-call timeout): retries share the budget."""
        return await asyncio.wait_for(aw, self.remaining())


@dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff with full jitter.

    ``attempts`` counts tries, not retries: ``attempts=1`` means no retry
    at all.  ``rand`` is injectable so tests get deterministic schedules
    (pass ``lambda: 1.0`` for the undithered envelope, ``lambda: 0.0``
    for zero-sleep retries)."""

    base_s: float = 0.05
    cap_s: float = 2.0
    factor: float = 2.0
    attempts: int = 8
    rand: object = field(default=random.random, repr=False, compare=False)

    def delay(self, attempt: int) -> float:
        """Backoff before try ``attempt + 1`` (attempt is 0-indexed)."""
        env = min(self.cap_s, self.base_s * (self.factor ** attempt))
        return env * float(self.rand())

    def delays(self, deadline: Deadline | None = None):
        """Yield the sleep before each RETRY (attempts - 1 values),
        stopping early once ``deadline`` has expired."""
        for attempt in range(self.attempts - 1):
            if deadline is not None and deadline.expired():
                return
            yield self.delay(attempt)


@dataclass(frozen=True)
class VerbBudgets:
    """Per-verb wall-clock budgets for control-plane calls.

    Budgets bound the WHOLE call — every redial, replay, and the server's
    execution — so they must dominate worst-case legitimate latency, not
    typical latency: a first ``tree_crawl`` on a cold compile cache
    pays a multi-minute XLA compile, and ``add_keys`` upload windows ride
    behind hundreds of in-flight peers.  The point is to convert an
    infinite hang (black-holed frames, a wedged peer) into a loud
    TimeoutError on a scale of minutes, not to police fast verbs."""

    default_s: float = 1800.0
    per_verb: dict = field(
        default_factory=lambda: {
            # cheap state verbs: no device work beyond a reset
            "reset": 300.0,
            "__hello__": 60.0,
            "status": 60.0,
            # dial + handshake verbs: bounded by the dial policy inside,
            # the budget is just the loud-failure backstop
            "plane_reset": 600.0,
        }
    )

    def budget(self, verb: str) -> float:
        return float(self.per_verb.get(verb, self.default_s))

    def deadline(self, verb: str) -> Deadline:
        return Deadline(self.budget(verb))


async def retry_async(
    fn,
    policy: RetryPolicy,
    *,
    what: str = "operation",
    deadline: Deadline | None = None,
    classify=is_transient,
):
    """Run ``await fn()`` under ``policy``: transient failures back off
    (full jitter) and retry until attempts or the shared ``deadline``
    run out; fatal failures and exhaustion re-raise the LAST error.

    Emits ``resilience.retry`` per retry so recovery behavior is visible
    in the structured log/run report, never only in a debugger."""
    attempt = 0
    while True:
        try:
            return await fn()
        except BaseException as e:  # classified below; re-raised when fatal
            if not classify(e):
                raise
            attempt += 1
            out_of_tries = attempt >= policy.attempts
            out_of_time = deadline is not None and deadline.expired()
            if out_of_tries or out_of_time:
                raise
            delay = policy.delay(attempt - 1)
            obs.emit(
                "resilience.retry",
                severity="debug",
                what=what,
                attempt=attempt,
                delay_s=round(delay, 4),
                error=f"{type(e).__name__}: {e}",
            )
            await asyncio.sleep(delay)


# the default dial policy: ~10 s of redialing (sum of undithered envelope
# ≈ 0.05·(1+2+4) + 2·6 ≈ 12 s ceiling, typically ~6 s with jitter) — the
# window a supervised restart or a chaos-severed listener needs to come
# back, without stalling a genuinely-down server for minutes
DIAL_POLICY = RetryPolicy(base_s=0.05, cap_s=2.0, factor=2.0, attempts=10)

# one TCP connect attempt: the OS SYN timeout is minutes; a LAN/localhost
# dial that hasn't completed in 5 s is dead — fail it and let the policy
# back off and redial
DIAL_TIMEOUT_S = 5.0

# mid-level shard retry (leader_rpc._shard_call): a transient data-plane
# fault re-keys the plane and re-runs JUST the lost shard.  Few attempts
# on purpose — each retry already rides the client's own redial/replay
# machinery, and a span that fails three times is a server problem the
# full recovery path (checkpoint rollback) owns
SHARD_POLICY = RetryPolicy(base_s=0.05, cap_s=1.0, factor=2.0, attempts=3)
