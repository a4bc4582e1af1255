"""Chaos proxy: deterministic fault injection between crawl sockets.

An asyncio TCP proxy that understands the control/data-plane framing as
far as a forwarder must (the 8-byte little-endian prefix over a frame's
whole body, protocol/wire.py ``HDR``; the body's inner layout, pickled
metadata followed by raw array buffers, is opaque here) and can
therefore trigger faults at exact FRAME boundaries — "sever the leader's
link right after the 12th request" is reproducible, where byte- or
time-triggered faults are not.

Fault grammar (the ``FHH_FAULTS`` env spec; ';'-separated clauses)::

    <link>:<action>@msg=<N>[,key=value...]

    link    label the proxy was constructed with (e.g. ctl0, ctl1, plane)
    action  sever | delay | blackhole | truncate | flood | slowclient
    msg=N   fire when the Nth frame (1-indexed, per direction) arrives
    dir=    c2s (default) | s2c — which direction's frame counter triggers
    ms=M    delay/slowclient: forward M milliseconds late (default 200)
    count=K blackhole: drop K consecutive frames then resume;
            flood: deliver K EXTRA copies of the trigger frame;
            slowclient: trickle K consecutive frames (default 1;
            sever/truncate ignore it — the connection is gone after one)

Actions:

- ``sever``     — close both sides mid-stream (RST-ish: the peer sees a
  reset/EOF).  The listener stays up: a reconnecting client redials
  through the same proxy and gets a clean new pipe.
- ``delay``     — hold one frame for ``ms`` before forwarding (tests
  deadline headroom without killing anything).
- ``blackhole`` — read and DROP ``count`` frames silently; the
  connection stays open (tests the per-verb wall-clock budgets: the
  caller must time out rather than hang forever).
- ``truncate``  — forward only half of the frame's payload bytes, then
  sever (tests the torn-frame path: the reader must classify the
  corrupt/short frame as transport loss, not crash).
- ``flood``     — deliver the trigger frame 1 + ``count`` times (the
  at-least-once delivery pathology made real: a duplicated
  ``submit_keys``/verb frame must be absorbed by the replay dedup /
  recorded-verdict machinery, never double-applied).
- ``slowclient`` — trickle the next ``count`` frames ``ms`` late EACH
  (a slow or throttled client; tests that a slow producer stalls only
  itself — the crawl and other clients keep moving).

Each accepted connection gets an independent pump per direction.  Frame
ORDINALS are per connection and per direction (deterministic: TCP orders
each direction), but the fault clauses themselves are consumed
PROXY-GLOBALLY — a sever that fired once does not re-arm on the redial
(otherwise a reconnecting client would be severed at the same ordinal of
every fresh connection, forever).  Chain clauses for multi-fault
schedules; ``ChaosProxy.sever_now()`` gives imperative test control.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

from .. import obs
from ..protocol.wire import HDR as _HDR  # the outer prefix: all a forwarder reads

_ACTIONS = ("sever", "delay", "blackhole", "truncate", "flood", "slowclient")
_DIRS = ("c2s", "s2c")


@dataclass(frozen=True)
class FaultSpec:
    link: str
    action: str
    at_msg: int  # 1-indexed frame ordinal that triggers the fault
    direction: str = "c2s"
    ms: int = 200
    count: int = 1

    def __post_init__(self):
        if self.action not in _ACTIONS:
            raise ValueError(f"unknown chaos action {self.action!r}")
        if self.direction not in _DIRS:
            raise ValueError(f"unknown chaos direction {self.direction!r}")
        if self.at_msg < 1:
            raise ValueError("msg= trigger is 1-indexed")


def parse_faults(spec: str) -> list[FaultSpec]:
    """Parse an ``FHH_FAULTS`` spec string (grammar above).  Empty/blank
    specs parse to no faults; malformed clauses raise ValueError loudly —
    a chaos schedule that silently no-ops would make a recovery test pass
    for the wrong reason."""
    out: list[FaultSpec] = []
    for clause in (spec or "").split(";"):
        clause = clause.strip()
        if not clause:
            continue
        try:
            head, args = clause.split("@", 1)
            link, action = head.split(":", 1)
        except ValueError:
            raise ValueError(
                f"bad chaos clause {clause!r} (want link:action@msg=N[,k=v...])"
            ) from None
        kw: dict = {}
        for part in args.split(","):
            k, _, v = part.partition("=")
            k, v = k.strip(), v.strip()
            if k == "msg":
                kw["at_msg"] = int(v)
            elif k == "dir":
                kw["direction"] = v
            elif k in ("ms", "count"):
                kw[k] = int(v)
            else:
                raise ValueError(f"unknown chaos arg {k!r} in {clause!r}")
        if "at_msg" not in kw:
            raise ValueError(f"chaos clause {clause!r} missing msg= trigger")
        out.append(FaultSpec(link=link.strip(), action=action.strip(), **kw))
    return out


class ChaosProxy:
    """One listener forwarding to one target, applying the fault clauses
    whose ``link`` matches this proxy's label.

    Construct, ``await start()``, point the client at ``listen_port``.
    The proxy survives severs (the listener stays bound) so reconnect
    paths are exercised end-to-end through the same chokepoint.
    """

    def __init__(
        self,
        listen_host: str,
        listen_port: int,
        target_host: str,
        target_port: int,
        faults: list[FaultSpec] | None = None,
        link: str = "link",
    ):
        self.listen_host, self.listen_port = listen_host, listen_port
        self.target_host, self.target_port = target_host, target_port
        self.link = link
        self.faults = [f for f in (faults or []) if f.link == link]
        self._srv: asyncio.AbstractServer | None = None
        self._conns: set[tuple] = set()
        self._pumps: set[asyncio.Task] = set()
        # armed faults are consumed proxy-globally: [spec, remaining_fires]
        # (blackhole/slowclient fire once per frame for count frames; the
        # rest fire once — flood's count multiplies within its one fire)
        self._armed: list[list] = [
            [f, f.count if f.action in ("blackhole", "slowclient") else 1]
            for f in self.faults
        ]
        self.frames = {"c2s": 0, "s2c": 0}  # lifetime totals, all conns
        self.fired: list[tuple[str, str, int]] = []  # (action, dir, msg#)

    async def start(self) -> "ChaosProxy":
        self._srv = await asyncio.start_server(
            self._on_client, self.listen_host, self.listen_port
        )
        return self

    async def stop(self) -> None:
        if self._srv is not None:
            self._srv.close()  # stop accepting
        # cut the accepted connections BEFORE waiting: wait_closed()
        # returns only once every connection of the listener is gone
        self.sever_now()
        for t in list(self._pumps):
            t.cancel()
        for t in list(self._pumps):
            try:
                await t
            # fhh-lint: disable=broad-except (teardown: a pump dying of
            # ANY error while being torn down is expected, not reportable)
            except (asyncio.CancelledError, Exception):
                pass
        if self._srv is not None:
            await self._srv.wait_closed()

    def sever_now(self) -> None:
        """Imperatively cut every live connection (keeps listening)."""
        for pair in list(self._conns):
            for w in pair:
                if not w.is_closing():
                    w.close()
        self._conns.clear()

    # -- internals --------------------------------------------------------

    async def _on_client(self, c_reader, c_writer):
        try:
            s_reader, s_writer = await asyncio.wait_for(
                asyncio.open_connection(self.target_host, self.target_port),
                5.0,
            )
        except (OSError, asyncio.TimeoutError):
            c_writer.close()
            return
        pair = (c_writer, s_writer)
        self._conns.add(pair)
        state = _ConnState(self)
        for direction, rd, wr in (
            ("c2s", c_reader, s_writer),
            ("s2c", s_reader, c_writer),
        ):
            t = asyncio.create_task(self._pump(state, direction, rd, wr, pair))
            self._pumps.add(t)
            t.add_done_callback(self._pumps.discard)

    def _sever_pair(self, pair) -> None:
        for w in pair:
            if not w.is_closing():
                w.close()
        self._conns.discard(pair)

    async def _pump(self, state, direction, reader, writer, pair):
        """Forward frames one at a time, consulting the schedule at each
        frame boundary.  Any transport error on either side ends the pump
        (and severs the pair: half-open proxies would hide real severs)."""
        try:
            while True:
                # fhh-lint: disable=unbounded-await (proxy pump: a chaos
                # proxy must never impose its own deadline — the system
                # under test owns all timeout behavior)
                hdr = await reader.readexactly(_HDR.size)
                (n,) = _HDR.unpack(hdr)
                # fhh-lint: disable=unbounded-await (as above)
                body = await reader.readexactly(n)
                msg_no = state.next_msg(direction)
                self.frames[direction] += 1
                fault = state.fault_for(direction, msg_no)
                if fault is not None:
                    self.fired.append((fault.action, direction, msg_no))
                    obs.emit(
                        "resilience.chaos_fired",
                        severity="debug",
                        link=self.link,
                        action=fault.action,
                        direction=direction,
                        msg=msg_no,
                    )
                    # fault events become trace instants: the injected
                    # sever/blackhole shows up ON the merged timeline at
                    # the exact frame it fired, next to the spans it
                    # errored (obs.trace; no-op when tracing is off)
                    obs.trace.instant(
                        f"chaos.{fault.action}", comp=f"chaos:{self.link}",
                        direction=direction, msg=msg_no,
                    )
                    if fault.action == "sever":
                        self._sever_pair(pair)
                        return
                    if fault.action == "blackhole":
                        continue  # drop the frame; connection stays up
                    if fault.action == "truncate":
                        writer.write(hdr + body[: max(1, n // 2)])
                        await writer.drain()
                        self._sever_pair(pair)
                        return
                    if fault.action in ("delay", "slowclient"):
                        await asyncio.sleep(fault.ms / 1000.0)
                    if fault.action == "flood":
                        # duplicate delivery: the frame arrives count
                        # EXTRA times (at-least-once made real) — the
                        # original forward below is the +1
                        for _ in range(max(1, fault.count)):
                            writer.write(hdr + body)
                        await writer.drain()
                writer.write(hdr + body)
                await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError, OSError):
            self._sever_pair(pair)


class _ConnState:
    """Per-connection frame counters; fault consumption lives on the
    proxy (``_armed``) so a fired fault stays fired across redials.  A
    blackhole of ``count=K`` drops the next K frames matching its
    direction once its trigger ordinal is reached."""

    def __init__(self, proxy: ChaosProxy):
        self.counts = {"c2s": 0, "s2c": 0}
        self._proxy = proxy

    def next_msg(self, direction: str) -> int:
        self.counts[direction] += 1
        return self.counts[direction]

    def fault_for(self, direction: str, msg_no: int) -> FaultSpec | None:
        for ent in self._proxy._armed:
            f, remaining = ent
            if remaining <= 0 or f.direction != direction:
                continue
            if msg_no >= f.at_msg:
                ent[1] -= 1
                return f
        return None


# ---------------------------------------------------------------------------
# Mesh (ICI) chaos: in-process fault injection for a multi-chip server
# (parallel/server_mesh.py)
#
# A server's own mesh has no sockets to proxy — its reductions are XLA
# collectives (psum) inside compiled programs, so faults are injected at
# the LEVEL boundaries the crawl verbs cross anyway
# (``CollectorServer._mesh_guard`` consults the injector before each
# level's dispatch).  Three surrogates for the real ICI failure modes:
#
# - ``drop``  — a dropped data-parallel shard: the level's collective
#   result cannot be trusted; device state (the frontier) is intact, so
#   recovery is "re-run the level" — the shard-granular cost.
# - ``kill``  — a donor device killed mid-all-gather: the injector
#   CLOBBERS the runner's device-resident frontier (the in-process
#   equivalent of losing a participating chip's HBM), so recovery must
#   restore from the last host checkpoint.
# - ``delay`` — a slow participant: the level stalls ``ms`` milliseconds
#   but completes; recovery must NOT trigger (tests the absence of
#   spurious rollbacks).
#
# Grammar (``FHH_MESH_FAULTS``): ``mesh:<action>@level=<N>[,ms=M]``,
# ';'-separated, consumed once each like the proxy's clauses.
# ---------------------------------------------------------------------------

_MESH_ACTIONS = ("drop", "kill", "delay")


class MeshFaultError(RuntimeError):
    """An injected (or detected) mesh-collective fault; ``state_lost``
    tells the supervisor whether the device-resident frontier survived
    (drop: re-run the level) or not (kill: restore a checkpoint)."""

    def __init__(self, msg: str, state_lost: bool = False):
        super().__init__(msg)
        self.state_lost = state_lost


@dataclass(frozen=True)
class MeshFaultSpec:
    action: str
    at_level: int
    ms: int = 200

    def __post_init__(self):
        if self.action not in _MESH_ACTIONS:
            raise ValueError(f"unknown mesh chaos action {self.action!r}")
        if self.at_level < 0:
            raise ValueError("level= trigger must be >= 0")


def parse_mesh_faults(spec: str) -> list:
    """Parse an ``FHH_MESH_FAULTS`` spec (grammar above).  Blank specs
    parse to no faults; malformed clauses raise ValueError loudly, same
    contract as :func:`parse_faults`."""
    out: list[MeshFaultSpec] = []
    for clause in (spec or "").split(";"):
        clause = clause.strip()
        if not clause:
            continue
        try:
            head, args = clause.split("@", 1)
            link, action = head.split(":", 1)
        except ValueError:
            raise ValueError(
                f"bad mesh chaos clause {clause!r} "
                "(want mesh:action@level=N[,ms=M])"
            ) from None
        if link.strip() != "mesh":
            raise ValueError(f"mesh chaos clause {clause!r} must target 'mesh'")
        kw: dict = {}
        for part in args.split(","):
            k, _, v = part.partition("=")
            k, v = k.strip(), v.strip()
            if k == "level":
                kw["at_level"] = int(v)
            elif k == "ms":
                kw["ms"] = int(v)
            else:
                raise ValueError(f"unknown mesh chaos arg {k!r} in {clause!r}")
        if "at_level" not in kw:
            raise ValueError(f"mesh chaos clause {clause!r} missing level=")
        out.append(MeshFaultSpec(action=action.strip(), **kw))
    return out


class MeshChaos:
    """Consumed-once mesh fault schedule.  ``before_level(runner, level)``
    is the hook ``CollectorServer._mesh_guard`` calls at each level
    entry, ``runner`` being the collection session; a clause whose
    ``at_level`` has been reached fires exactly once (re-run levels do
    not re-trigger it — the recovery must be able to make progress,
    exactly like the proxy's fired severs)."""

    def __init__(self, faults: list | None = None):
        self._armed: list[MeshFaultSpec] = list(faults or [])
        self.fired: list[tuple[str, int]] = []  # (action, level)

    def before_level(self, runner, level: int) -> None:
        for f in list(self._armed):
            if level < f.at_level:
                continue
            self._armed.remove(f)
            self.fired.append((f.action, level))
            obs.emit(
                "resilience.mesh_chaos_fired",
                severity="debug",
                action=f.action,
                level=level,
            )
            # mesh faults are trace instants too (see ChaosProxy._pump)
            obs.trace.instant(
                f"chaos.mesh_{f.action}", comp="chaos:mesh", level=level,
            )
            if f.action == "delay":
                time.sleep(f.ms / 1000.0)
                continue
            if f.action == "kill":
                # the donor's HBM is gone: clobber the device frontier so
                # any recovery short of a checkpoint restore fails loudly
                runner.frontier = None
                runner._children = None
                raise MeshFaultError(
                    f"mesh participant killed mid-collective at level "
                    f"{level}", state_lost=True,
                )
            raise MeshFaultError(
                f"data-parallel shard dropped at level {level}",
                state_lost=False,
            )


# ---------------------------------------------------------------------------
# Host chaos: whole-collector-pair loss (the fleet failover drill)
#
# Above the connection layer (ChaosProxy severs one link) and the device
# layer (MeshChaos clobbers one participant) sits the host: BOTH servers
# of a collector pair vanishing at once — a rack power loss, a preempted
# VM pair.  The surrogate is driven by the windowed ingest driver at its
# window boundaries (the same place the mesh injector uses level
# boundaries): a clause whose ``at_window`` has been reached fires once,
# and the harness kills the whole pair — the supervisor's probe then
# sees dead boot ids and fails the orphaned sessions over to a surviving
# pair (protocol/fleet.py) from their newest checkpoints.
#
# Grammar (``FHH_HOST_FAULTS``): ``host:kill@window=<N>``, ';'-separated,
# consumed once each like the mesh clauses.
# ---------------------------------------------------------------------------

_HOST_ACTIONS = ("kill",)


@dataclass(frozen=True)
class HostFaultSpec:
    action: str
    at_window: int

    def __post_init__(self):
        if self.action not in _HOST_ACTIONS:
            raise ValueError(f"unknown host chaos action {self.action!r}")
        if self.at_window < 0:
            raise ValueError("window= trigger must be >= 0")


def parse_host_faults(spec: str) -> list:
    """Parse an ``FHH_HOST_FAULTS`` spec (grammar above).  Blank specs
    parse to no faults; malformed clauses raise ValueError loudly, same
    contract as :func:`parse_faults`/:func:`parse_mesh_faults`."""
    out: list[HostFaultSpec] = []
    for clause in (spec or "").split(";"):
        clause = clause.strip()
        if not clause:
            continue
        try:
            head, args = clause.split("@", 1)
            link, action = head.split(":", 1)
        except ValueError:
            raise ValueError(
                f"bad host chaos clause {clause!r} "
                "(want host:kill@window=N)"
            ) from None
        if link.strip() != "host":
            raise ValueError(f"host chaos clause {clause!r} must target 'host'")
        kw: dict = {}
        for part in args.split(","):
            k, _, v = part.partition("=")
            k, v = k.strip(), v.strip()
            if k == "window":
                kw["at_window"] = int(v)
            else:
                raise ValueError(f"unknown host chaos arg {k!r} in {clause!r}")
        if "at_window" not in kw:
            raise ValueError(f"host chaos clause {clause!r} missing window=")
        out.append(HostFaultSpec(action=action.strip(), **kw))
    return out


class HostChaos:
    """Consumed-once host-pair fault schedule.  ``before_window(w)``
    returns True when a clause fires for this boundary — the caller
    (test harness / supervisor drill) then kills the whole pair; the
    injector itself stays process-agnostic because "a host" may be two
    in-process servers (tests) or two real processes (bin/server)."""

    def __init__(self, faults: list | None = None):
        self._armed: list[HostFaultSpec] = list(faults or [])
        self.fired: list[tuple[str, int]] = []  # (action, window)

    def before_window(self, window: int) -> bool:
        hit = False
        for f in list(self._armed):
            if window < f.at_window:
                continue
            self._armed.remove(f)
            self.fired.append((f.action, window))
            obs.emit(
                "resilience.host_chaos_fired",
                severity="debug",
                action=f.action,
                window=window,
            )
            obs.trace.instant(
                f"chaos.host_{f.action}", comp="chaos:host", level=window,
            )
            hit = True
        return hit


@dataclass
class ChaosLinks:
    """Convenience bundle for the standard three-link topology: leader→s0,
    leader→s1, s0→s1 data plane — built from one ``FHH_FAULTS`` string.
    ``await start()`` brings all three up; address helpers give the
    through-proxy endpoints the leader/server configs should dial."""

    listen_host: str
    base_port: int  # three consecutive ports: ctl0, ctl1, plane
    ctl0_target: tuple[str, int]
    ctl1_target: tuple[str, int]
    plane_target: tuple[str, int]
    faults: list[FaultSpec] = field(default_factory=list)
    proxies: dict = field(default_factory=dict)

    async def start(self) -> "ChaosLinks":
        for i, (link, tgt) in enumerate(
            (
                ("ctl0", self.ctl0_target),
                ("ctl1", self.ctl1_target),
                ("plane", self.plane_target),
            )
        ):
            p = ChaosProxy(
                self.listen_host,
                self.base_port + i,
                tgt[0],
                tgt[1],
                self.faults,
                link=link,
            )
            self.proxies[link] = await p.start()
        return self

    async def stop(self) -> None:
        for p in self.proxies.values():
            await p.stop()

    def addr(self, link: str) -> tuple[str, int]:
        order = ("ctl0", "ctl1", "plane")
        return self.listen_host, self.base_port + order.index(link)
