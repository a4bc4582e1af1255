"""The system under test, brought up as ``bin/server`` x2 + ``bin/leader`` wrap
it: ``rpc.CollectorServer`` 0 and 1, two ``rpc.CollectorClient``s and an
``RpcLeader`` in ONE process (one process holds the chip) over real localhost
sockets; s1 listens on the data plane, then s0 dials, bin/server's ordering.

``BenchLeader`` is the leader with a tap on its level loop: it keeps what the
harness needs of every level (host clock at its start and end, each server's
seconds in the program spans the cell's metric files name, the frontier and
counts held after it) and ends the crawl when the phase's rule says so.  It
hangs off ``RpcLeader._run_one_level`` because the leader has no public
per-level hook yet (PERF.md, tracing list).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import socket
import time

import jax

from fuzzyheavyhitters_tpu.protocol import rpc
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader

from readers import Level


class Stop(Exception):
    """Raised after a level to end the crawl there (the leader has no
    partial crawl of its own)."""


class BenchLeader(RpcLeader):
    crawl = 0                       # which crawl of the phase this is
    records: list                   # Levels of the current phase
    registries: dict                # {name: obs Registry} whose spans are read
    span_names: tuple = ()
    after_level = staticmethod(lambda rec: False)   # True: stop the crawl

    def _spans_now(self) -> dict:
        return {
            reg: {s: r.timer_seconds(s) for s in self.span_names}
            for reg, r in self.registries.items()
        }

    async def _run_one_level(self, level, nreqs, thresh):
        rec = Level(crawl=self.crawl, level=int(level))
        before = self._spans_now()
        with jax.profiler.TraceAnnotation("bench_level", level=int(level)):
            rec.t0 = time.perf_counter()
            try:
                counts, alive = await super()._run_one_level(level, nreqs, thresh)
            except Exception as e:
                rec.t1 = time.perf_counter()
                rec.error = f"{type(e).__name__}: {e}"
                self.records.append(rec)
                raise
            rec.t1 = time.perf_counter()
        after = self._spans_now()
        rec.spans = {
            reg: {s: after[reg][s] - before[reg][s] for s in self.span_names}
            for reg in after
        }
        rec.bucket = int(self._f_bucket)
        if counts is not None:
            # _run_one_level builds a new array each level: no copy needed
            rec.paths, rec.counts = self.paths, counts
        self.records.append(rec)
        if self.after_level(rec):
            raise Stop
        return counts, alive


def _free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


@contextlib.asynccontextmanager
async def pair(cfg, span_names):
    """(leader, server0, server1), connected and reset; torn down on exit."""
    p0, p1, pd = _free_ports(3)
    cfg = dataclasses.replace(
        cfg, server0=f"127.0.0.1:{p0}", server1=f"127.0.0.1:{p1}"
    )
    s0, s1 = rpc.CollectorServer(0, cfg), rpc.CollectorServer(1, cfg)
    clients = []
    try:
        t1 = asyncio.create_task(s1.start("127.0.0.1", p1, "127.0.0.1", pd))
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(s0.start("127.0.0.1", p0, "127.0.0.1", pd))
        clients.append(await rpc.CollectorClient.connect("127.0.0.1", p0))
        clients.append(await rpc.CollectorClient.connect("127.0.0.1", p1))
        await asyncio.gather(t0, t1)
        lead = BenchLeader(cfg, *clients)
        lead.records = []
        lead.span_names = tuple(span_names)
        lead.registries = {"server0": s0.obs, "server1": s1.obs}
        await asyncio.gather(*(c.call("reset") for c in clients))
        yield lead, s0, s1
        # drop the servers' device state before the process goes on
        await asyncio.gather(*(c.call("reset") for c in clients))
    finally:
        for c in clients:
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()


async def crawl(lead: BenchLeader, n: int, after_level) -> bool:
    """One crawl under ``after_level``; True when it ran to its own end."""
    lead.after_level = after_level
    try:
        await lead.run(n)
    except Stop:
        return False
    finally:
        lead.crawl += 1
    return True
