"""Headline benchmarks on the real chip.

Prints ONE JSON line.  Headline metric (continuity with rounds 1-2 and the
north star "client-keys/sec/chip at data_len=512"): ibDCF keygen
throughput vs the reference's single-threaded AES-NI baseline
(99.97 µs/key, src/bin/benchmarks/ibDCFbench.csv:5, BASELINE.md).  The
``extra`` field carries the rest of the reference's benchmark surface:

- the full keygen sweep data_len ∈ {64, 256, 512, 1024} with per-key wire
  bytes (the ibDCFbench.rs:55-70 sweep + bincode size column);
- ``aggregate_clients_per_sec``: the SERVER hot loop — a full
  data_len=512 trusted-mode crawl (expand -> exchange -> count ->
  threshold -> prune/advance per level) over N clients on one chip,
  measured back-to-back on BOTH expand engines (pack-in-kernel Pallas
  default vs XLA);
- ``crawl_hbm_max``: a REAL measured crawl (no projections) at the
  1-chip HBM maximum on BASELINE config 4's workload shape (zipf 10000
  sites, t=0.001, L=512) via the streaming mode — host-resident keys,
  per-level cw upload, chunked re-expand advance;
- ``secure_crawl``: the level loop with the REAL GC+OT data plane between
  two in-process collector servers over localhost sockets (e2e — the
  fused output-label b2a makes a level ONE protocol round trip; it still
  pays ~3 serial device<->host fetches per level, see ``secure_device``
  for the device-only number);
- ``secure_device``: the whole per-level 2PC as one on-chip program at
  flagship shape (>= 65k clients, L >= 64, plus an L=512-key level) —
  the 1-chip stand-in for the 2-chip mesh deployment;
- ``multichip``: secure clients/sec with each collector server's client
  axis sharded over 1/2/4/8 local data devices
  (``Config.server_data_devices``, parallel/server_mesh.py), every leg
  gated on bit-identity vs the single-device leg, with the pre-wire ICI
  reduction's seconds on the compact line;
- ``hbm``: the 1M-client HBM plan VALIDATED by allocation — the L=512
  key batch at the largest bench N actually lives on the chip, 3 levels
  run, and bytes/client are measured, not derived;
- ``hash_margin``: measured garbling cost at ChaCha rounds 8/12/20 (the
  margin note in ops/prg.py cites these);
- ``upload``: 1M-key control-plane ingest through the rolling window.

ONE PROCESS PER CHIP: a chip belongs to one process at a time, so the parent
(``main``) never initialises a JAX backend — importing this module only sets
config (``compile_cache.enable()``), and every leg, the keygen headline
included, runs in its own child process (``_subprocess_metric``), serially.
A leg whose child died or timed out reports ``{"error": ...}`` and makes the
exit code non-zero; skips for budget/sections/smoke stay 0.
"""

import json
import os
import time

import numpy as np

from fuzzyheavyhitters_tpu.ops import prg as _prg
from fuzzyheavyhitters_tpu.utils import compile_cache as _compile_cache

# bench targets the real chip: unrolled ChaCha rounds are ~6% faster there
# (the scan form is the compile-friendly default for test hosts, ops/prg.py)
_prg.CHACHA_UNROLL = True

# wall-clock budget: the whole bench must finish (and print its final
# parseable JSON line) inside this many seconds.  The harness runs bench
# under an external `timeout` that KILLs shortly after its TERM — a bench
# that overruns leaves NO artifact (BENCH_r05: rc=124, no JSON) — so the
# budget proactively trims the LATER, more expensive sections instead:
# each skipped section reports {"skipped": "budget"} and the final line
# still prints.  Override with FHH_BENCH_BUDGET=<seconds>.
BENCH_BUDGET_S = float(os.environ.get("FHH_BENCH_BUDGET", "3000"))
# seconds held back for the final artifact (report write + JSON print)
_BUDGET_RESERVE_S = 45.0
_BENCH_T0 = time.monotonic()
# CI smoke mode: tiny shapes, CPU-safe engines, heavyweight sections
# skipped — exercises the end-to-end bench contract (JSON line, budget,
# telemetry) in minutes on any host (scripts/bench_smoke.sh)
BENCH_SMOKE = os.environ.get("FHH_BENCH_SMOKE", "0") != "0"


def _budget_left() -> float:
    return BENCH_BUDGET_S - (time.monotonic() - _BENCH_T0)


# parent and child sections import this module first thing: place the
# persistent compile cache (config only — no backend is initialised)
# before any jit runs.
_compile_cache.enable()


BASELINE_US_PER_KEY = {64: None, 128: 25.92, 256: 50.47, 512: 99.97, 1024: 216.25}
BASELINE_KEYS_PER_SEC = 1e6 / 99.97  # ibDCFbench.csv:5 (data_len=512)
# reference per-key wire bytes (bincode), ibDCFbench.csv
BASELINE_KEY_BYTES = {128: 2585, 256: 5145, 512: 10265, 1024: 20505}


def _keygen_engine() -> str:
    """Fused Pallas kernel on a real chip; the host NumPy mirror elsewhere
    (no Mosaic on XLA:CPU — and the jax scan engine compiles pathologically
    there, see tests/conftest.py)."""
    from fuzzyheavyhitters_tpu.ops import ibdcf

    return ibdcf.best_engine()


def _key_wire_bytes(k0) -> int:
    """Per-key bytes of our wire format (one key = one (client, dim, side)
    slice of the batch; cf. the reference's bincode size probe,
    ibDCFbench.rs:67).  Metadata-only — fetching the batch to count bytes
    would pull GBs from the device."""
    per = 0
    for leaf in k0:
        shape, itemsize = leaf.shape, leaf.dtype.itemsize
        per += itemsize * int(np.prod(shape[1:])) if shape else itemsize
    return per


def _time_of(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _steady_state_seconds(thunk, force, warm_force, iters=20, trials=3):
    """Min-of-trials per-launch seconds for a device thunk.

    Queues ``iters`` launches and forces them with ONE sync whose value
    depends on every launch (``force`` maps the list of outputs to a host
    int).  A per-iteration scalar fetch adds a device->host round trip to
    each measurement.  The dependent sync is honest and amortized; the MIN
    over trials strips additive queueing noise (which otherwise
    swings results 3-5x)."""
    warm_force(thunk())  # compile + warm
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        force([thunk() for _ in range(iters)])
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _throughput(jnp, gen, seeds_d, alpha_d, side_d, n, iters=32, trials=3):
    """Steady-state keygen keys/sec (see _steady_state_seconds).

    The queued thunk reduces the generated keys to ONE device scalar
    inside the same jit program: the sum depends on the whole (opaque)
    keygen kernel, so nothing is dead-code-eliminated, but the ~20 B/key
    cw tensors are program-internal temporaries — freed as each launch
    retires — so a DEEP queue (amortizing the end-of-batch fetch RTT over
    ``iters``) coexists with production-sized batches instead of trading
    off against HBM for queued outputs."""
    import jax

    k0, _ = gen(seeds_d, alpha_d, side_d)  # un-queued: the wire-size probe

    @jax.jit
    def summed(s, a, sd):
        return jnp.sum(gen(s, a, sd)[0].cw_seed.astype(jnp.uint32))

    best = _steady_state_seconds(
        lambda: summed(seeds_d, alpha_d, side_d),
        lambda outs: int(sum(outs[1:], start=outs[0])),
        lambda o: int(o),
        iters=iters,
        trials=trials,
    )
    return n / best, k0


def bench_keygen(jax, jnp, ibdcf, rng, sweep=(64, 128, 256, 512, 1024)):
    from fuzzyheavyhitters_tpu.ops.keygen_pallas import gen_pair_pallas

    rows = {}
    headline = None
    for L in sweep:
        # PRODUCTION-shaped batches: the leader generates keys 32k-128k at
        # a time (bench_crawl_hbm_max, bin/leader.py's report).  Small
        # batches measure the per-launch dispatch overhead, not
        # the kernel — observed to swing 1-15 ms by day, which at n=8192
        # (5.8 ms of kernel work) once read as a 3x kernel "regression".
        # The ~20 B/key outputs are launch-internal temporaries (see
        # _throughput), so the queue stays DEEP at these sizes.
        n = 131072 if L >= 1024 else 262144
        alpha = rng.integers(0, 2, size=(n, L)).astype(bool)
        seeds = rng.integers(0, 2**32, size=(n, 2, 4), dtype=np.uint32)
        side = np.ones(n, bool)
        alpha_d, seeds_d, side_d = map(jax.device_put, (alpha, seeds, side))

        keys_per_sec, k0 = _throughput(
            jnp, gen_pair_pallas, seeds_d, alpha_d, side_d, n,
            trials=6 if L == 512 else 3,  # headline: more min-of-trials
            # insurance against cross-run queueing variance
        )
        base = BASELINE_US_PER_KEY.get(L)
        rows[L] = {
            "keys_per_sec": round(keys_per_sec, 1),
            "us_per_key": round(1e6 / keys_per_sec, 3),
            "key_bytes": _key_wire_bytes(k0),
            "n": n,
            "vs_baseline": round(keys_per_sec / (1e6 / base), 2) if base else None,
        }
        if L == 512:  # headline size only: also compare the scan engine
            # (each extra engine is another compile)
            scan_kps, _ = _throughput(
                jnp, ibdcf.gen_pair, seeds_d, alpha_d, side_d, n, iters=6
            )
            rows[L]["scan_engine_keys_per_sec"] = round(scan_kps, 1)
            headline = keys_per_sec
    return headline, rows


def bench_keygen_leg():
    """The keygen leg as its child process runs it (main() spawns it like
    every other leg): ``{"headline": keys/s at L=512, "sweep": rows}``."""
    rng = np.random.default_rng(0)
    if BENCH_SMOKE:
        headline, sweep = bench_keygen_smoke(rng)
    else:
        import jax
        import jax.numpy as jnp

        from fuzzyheavyhitters_tpu.ops import ibdcf

        headline, sweep = bench_keygen(jax, jnp, ibdcf, rng)
    return {"headline": headline, "sweep": sweep}


def write_keygen_csv(rows: dict, path: str = "ibDCFbench_tpu.csv"):
    """Emit the sweep in the shape of the reference's one shipped benchmark
    artifact (ibDCFbench.rs:57-68 -> ibDCFbench.csv: string_length,
    number_keys, time, avg_time, size)."""
    with open(path, "w") as f:
        f.write("string_length,number_keys,time,avg_time,size\n")
        for L in sorted(rows, key=int):  # JSON round trips make keys str
            r = rows[L]
            avg = 1.0 / r["keys_per_sec"]
            n = r["n"]
            f.write(f"{L},{n},{avg * n},{avg},{r['key_bytes']}\n")


def bench_crawl(ibdcf, driver, rng, n=131072, L=512, f_max=64):
    """Server hot loop: full L-level trusted-mode crawl on one chip.

    Zipf-like scenario: clients cluster on a handful of sites so the
    frontier stays small (the production regime) while every level still
    expands/compares all N clients.  Round-4 shape of the measurement:

    - the frontier is BUCKETED (collect.bucket_for) and advance is a
      gather from the expand-time child cache — per-level work is sized
      to survivors, with no second PRG pass;
    - N = 131072 so per-level COMPUTE dominates the per-dispatch
      floor (~2 ms/launch; at the old N=8192 that floor was most of the
      measured "device" time, silently inflating the 1M projection 16x
      more than compute justifies);
    - the level pipeline is ONE jitted program (both servers' expand +
      counts + both advances), matching the production mesh path where
      counts_body is a single XLA dispatch per level (parallel/mesh.py).
    """
    n_sites = 4
    sites = rng.integers(0, 2, size=(n_sites, 1, L)).astype(bool)
    pts_bits = sites[rng.integers(0, n_sites, size=n)]
    # keygen on the chip (the fused kernel): host NumPy keygen for 512-bit
    # interval pairs at this N takes hours on a 1-core host
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine=_keygen_engine())

    import jax
    import jax.numpy as jnp

    from fuzzyheavyhitters_tpu.protocol import collect

    timed_levels = min(64, L)

    def run_slice(levels):
        s0, s1 = driver.make_servers(k0, k1)
        lead = driver.Leader(s0, s1, n_dims=1, data_len=L, f_max=f_max)
        lead.tree_init()
        t0 = time.perf_counter()
        for lvl in range(levels):
            n_alive = lead.run_level(lvl, nreqs=n, threshold=0.05)
            assert n_alive >= 1  # early levels hold few nodes (2^level caps)
        return time.perf_counter() - t0, n_alive, s0, s1

    def measure_engine(want_fit=True):
        """Steady-state per-level seconds under the CURRENT engine knob.

        Warm slice compiles every bucket size of the steady crawl
        (1 -> 2 -> 4 ... as the sites' prefixes separate); the second,
        timed, slice replays the same buckets; then the device-only level
        pipeline runs on the steady-state frontier the slice left behind
        (idempotent: same inputs each launch) — ONE fused program covering
        BOTH servers, so the per-server cost is half of this.
        """
        run_slice(timed_levels)
        dt_slice, n_alive, s0, s1 = run_slice(timed_levels)
        # by level 64 the 4 random sites' prefixes are distinct w.h.p.,
        # and each survives with its ball neighbours
        assert n_alive >= n_sites
        masks = jnp.asarray(collect.pattern_masks(1))
        alive = jnp.asarray(s0.alive_keys)
        nb = collect.bucket_for(n_alive, f_max)
        parent = jnp.zeros(nb, jnp.int32)
        pat = jnp.zeros((nb, 1), bool)

        @jax.jit
        def one_level(keys0, f0, keys1, f1, lvl):
            p0, ch0 = collect.expand_share_bits(keys0, f0, lvl)
            p1, ch1 = collect.expand_share_bits(keys1, f1, lvl)
            cnt = collect.counts_by_pattern(p0, p1, masks, alive, f0.alive)
            nf0 = collect.advance_from_children(ch0, parent, pat, n_alive)
            nf1 = collect.advance_from_children(ch1, parent, pat, n_alive)
            return cnt, nf0, nf1

        # 64 queued launches per sync: the end-of-batch fetch is one
        # device->host round trip, amortized over the batch
        best = _steady_state_seconds(
            lambda: one_level(s0.keys, s0.frontier, s1.keys, s1.frontier,
                              timed_levels),
            lambda outs: int(sum(jnp.sum(c[0, 0]) for c, _, _ in outs)),
            lambda o: int(jnp.sum(o[0])),
            iters=64,
        )

        if not want_fit:  # A/B comparison pass: skip the 2x-bucket point
            return best, None, dt_slice, s0.frontier.f_bucket

        # second point at DOUBLE the frontier bucket (same keys, same
        # clients — per-client work doubles): separates the per-launch
        # dispatch overhead from the kernel's marginal cost, for honest
        # amortized projections (linear n/dt scaling charges the 1M
        # target the 131k run's overhead 7.6x over)
        def grow(fr):
            st = fr.states
            if collect._expand_engine():  # planar [.., F, N] node axis -4/-2
                dup = lambda a, ax: jnp.concatenate([a, a], axis=ax)
                states = type(st)(
                    seed=dup(st.seed, -2), bit=dup(st.bit, -2),
                    y_bit=dup(st.y_bit, -2),
                )
            else:
                dup = lambda a: jnp.concatenate([a, a], axis=0)
                states = type(st)(*[dup(x) for x in st])
            return collect.Frontier(
                states=states, alive=jnp.concatenate([fr.alive, fr.alive])
            )

        f0b, f1b = grow(s0.frontier), grow(s1.frontier)
        parent2 = jnp.zeros(2 * nb, jnp.int32)
        pat2 = jnp.zeros((2 * nb, 1), bool)

        @jax.jit
        def one_level2(keys0, fr0, keys1, fr1, lvl):
            p0, ch0 = collect.expand_share_bits(keys0, fr0, lvl)
            p1, ch1 = collect.expand_share_bits(keys1, fr1, lvl)
            cnt = collect.counts_by_pattern(p0, p1, masks, alive, fr0.alive)
            nf0 = collect.advance_from_children(ch0, parent2, pat2, 2 * n_alive)
            nf1 = collect.advance_from_children(ch1, parent2, pat2, 2 * n_alive)
            return cnt, nf0, nf1

        one_level2(s0.keys, f0b, s1.keys, f1b, timed_levels)
        # SAME iters as the first point: the end-of-batch sync RTT
        # amortizes identically into both, so the two-point difference
        # isolates the marginal cost instead of absorbing RTT/iters skew
        best2 = _steady_state_seconds(
            lambda: one_level2(s0.keys, f0b, s1.keys, f1b, timed_levels),
            lambda outs: int(sum(jnp.sum(c[0, 0]) for c, _, _ in outs)),
            lambda o: int(jnp.sum(o[0])),
            iters=64,
        )
        return best, best2, dt_slice, s0.frontier.f_bucket

    # back-to-back engine A/B (the only meaningful comparison on the
    # shared chip, whose throughput swings ~4x by hour): the XLA engine
    # first, then the pack-in-kernel Pallas engine — the default — last,
    # so the headline numbers come from the default engine's run.  On a
    # CPU-only host both knob settings resolve to the XLA engine
    # (collect._expand_engine), so the A/B would compare a thing to
    # itself — skip it and report one engine.
    default_engine = collect.EXPAND_PALLAS
    collect.EXPAND_PALLAS = True
    two_engines = collect._expand_engine()
    try:
        if two_engines:
            collect.EXPAND_PALLAS = False
            best_xla, _, _, _ = measure_engine(want_fit=False)
            collect.EXPAND_PALLAS = True
        best, best2, dt_slice, f_bucket = measure_engine()
    finally:
        collect.EXPAND_PALLAS = default_engine
    dt = best * L
    ab = (
        {
            "ms_per_level_device_xla_engine": round(best_xla * 1000, 3),
            "engine_speedup_vs_xla": round(best_xla / best, 2),
        }
        if two_engines
        else {}
    )
    # launch-overhead split from the two bucket points: per-client
    # marginal cost = best2 - best (the doubled bucket doubles every
    # client's states), fixed per-launch = the remainder.  The naive
    # linear projection charges the 1M target the fixed overhead
    # (1M/n)x; the amortized projections charge it once per launch.
    # If chip noise makes best2 <= best the fit is DEGENERATE — fall
    # back to the (conservative) linear projection and say so, rather
    # than reporting 1M clients as free.
    fit_ok = best2 > best
    if fit_ok:
        marg = best2 - best  # per n clients at f_bucket
        fixed = max(best - marg, 0.0)
        t_1m_level = fixed + marg * (1_000_000 / n)
        t_125k_level = fixed + marg * (125_000 / n)
    else:
        t_1m_level = best * (1_000_000 / n)
        t_125k_level = best * max(125_000 / n, 1.0)
        fixed = 0.0
    return {
        "aggregate_clients_per_sec": round(n / dt, 1),
        "crawl_seconds_device": round(dt, 3),
        "ms_per_level_device": round(best * 1000, 3),
        **ab,
        "ms_per_level_device_2x_bucket": round(best2 * 1000, 3),
        "launch_overhead_ms": round(fixed * 1000, 3),
        "overhead_fit_degenerate": not fit_ok,
        "ms_per_level_e2e": round(dt_slice / timed_levels * 1000, 2),
        "timed_levels_e2e": timed_levels,
        "n_clients": n,
        "data_len": L,
        "f_bucket_steady": int(f_bucket),
        "levels_per_sec": round(L / dt, 2),
        "projected_1m_clients_seconds_1chip": round(dt * (1_000_000 / n), 1),
        # compute-amortized: one launch per level carries all clients (the
        # streaming mode's regime; 1M clients' keys need ~2 chips of HBM
        # or host streaming, so this is the COMPUTE bound, overhead paid
        # once per level, marginal cost scaled from the measured 2-point
        # fit above)
        "projected_1m_clients_seconds_1chip_amortized": round(
            t_1m_level * L, 1
        ),
        # the north star (BASELINE.json): clients are data-parallel over
        # the mesh's `data` axis (parallel/mesh.py) — per-level cross-chip
        # traffic is one psum of the [F, 2^d] count shares, microseconds
        # against a multi-ms level — so 8 chips each crawl 125k clients
        # in parallel, each paying the per-launch overhead once per level
        "projected_1m_clients_seconds_v5e8": round(t_125k_level * L, 1),
    }



def bench_crawl_hbm_max(rng, n=196608, L=512, sites=10000, threshold=0.001,
                        zipf_exp=1.03, ball=2, aug=8):
    """REAL measured crawl at the 1-chip HBM maximum — no projections.

    BASELINE.json config 4's workload shape (zipf over 10000 sites,
    data_len=512, threshold=0.001) at the largest client count one chip
    can hold with BOTH servers colocated.  The round-4 HBM plan projected
    ~663k clients from per-SERVER key bytes; this chip carries both
    parties, and the binding constraint is frontier state
    (F x N x d x 2 x 18 B x 2 servers x old+new), not keys: the
    thresholded frontier is ~103 nodes steady (measured), but near the
    LEAVES the ball-size-2 neighbourhoods multiply survivors ~4x (103 ->
    421 hitters -> bucket 512), and that late-crawl spike is what sizes
    memory — 320k clients OOMed around level 450 on exactly it; 196k is
    the measured fit.  The run uses the STREAMING mode
    (protocol/driver.py): keys live in host RAM (8 GB for both servers),
    each level uploads only its ~40 B/client cw slice (double-buffered
    behind the expands), and advance re-expands survivors chunk-wise
    (collect.advance_from_cw) so the transient stays bounded.  Keygen runs
    chunked on the chip and lands key chunks in host RAM as it goes.

    Every number reported is measured wall-clock, INCLUDING the Python
    client simulation, keygen + device->host key fetch, and per-level
    host thresholding; per-level compile costs (first occurrence of each
    bucket shape) are inside the e2e time, so the steady-state rate is
    reported as the median level."""
    import jax

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import driver
    from fuzzyheavyhitters_tpu.workloads import strings

    t0 = time.perf_counter()
    pts, _ = strings.zipf_workload(rng, sites, L, 1, zipf_exp, n, aug)
    t_sim = time.perf_counter() - t0

    # chunked keygen: the full cw tensor (2 x 9.4 GB) cannot sit on the
    # chip next to the crawl; generate 32k clients at a time and fetch
    host = lambda k: type(k)(*[np.asarray(x) for x in k])
    t0 = time.perf_counter()
    ch = 32768
    parts = []
    for i in range(0, n, ch):
        k0c, k1c = ibdcf.gen_l_inf_ball(
            pts[i : i + ch], ball, rng, engine=_keygen_engine()
        )
        parts.append((host(k0c), host(k1c)))
        del k0c, k1c
    cat = lambda xs: type(xs[0])(
        *[np.concatenate([np.asarray(l) for l in leaves], axis=0)
          for leaves in zip(*xs)]
    )
    k0 = cat([p[0] for p in parts])
    k1 = cat([p[1] for p in parts])
    del parts
    t_keygen = time.perf_counter() - t0

    s0, s1 = driver.make_servers(k0, k1)
    lead = driver.Leader(
        s0, s1, n_dims=1, data_len=L, f_max=1024, min_bucket=128,
        stream=True, stream_chunk=32,
    )
    lead.tree_init()
    t0 = time.perf_counter()
    level_s = []
    for lvl in range(L):
        t1 = time.perf_counter()
        n_alive = lead.run_level(lvl, nreqs=n, threshold=threshold)
        level_s.append(time.perf_counter() - t1)
        if lvl % 64 == 0:
            from fuzzyheavyhitters_tpu import obs

            obs.emit(
                "bench.level", level=lvl, alive=int(n_alive),
                seconds=round(level_s[-1], 2),
            )
        if n_alive == 0:
            break
    dt = time.perf_counter() - t0
    med = float(np.median(level_s))
    # per-phase split from the driver's telemetry registry (obs layer):
    # fss = expand, field = counts/threshold, advance = frontier rebuild.
    # Leaf phases only — the enclosing "level" span is their sum and
    # would double-count for any consumer adding the reported phases.
    rep_phases = lead.obs.report()["phases"]
    phase_seconds = {
        k: round(rep_phases[k]["seconds"], 2)
        for k in ("fss", "field", "advance")
        if k in rep_phases
    }
    return {
        "n_clients": n,
        "data_len": L,
        "num_sites": sites,
        "threshold": threshold,
        "phase_seconds": phase_seconds,
        "device_fetches": int(lead.obs.counter_value("device_fetches")),
        "hitters": int(lead.n_nodes),
        "crawl_seconds_e2e": round(dt, 1),
        "clients_per_sec_e2e": round(n / dt, 1),
        "ms_per_level_median": round(med * 1000, 1),
        "clients_per_sec_steady": round(n / (med * L), 1),
        "levels_run": len(level_s),
        "f_bucket_steady": int(s0.frontier.f_bucket),
        "client_sim_seconds": round(t_sim, 2),
        "keygen_and_fetch_seconds": round(t_keygen, 1),
        "host_key_gbytes_both_servers": round(
            sum(np.asarray(x).nbytes for k in (k0, k1) for x in k) / 1e9, 2
        ),
    }


def bench_covid(n=8192, L=64, n_counties=64, ball=1, threshold=0.01):
    """COVID-geo workload end to end on the chip: the f64-bit domain
    (data_len=64, n_dims=2 — ref: sample_covid_data.rs:32-35) through the
    full driver crawl.  The reference's own covid call site is commented
    out (leader.rs:367-371), so this is parity-plus: a hot-county centroid
    file, jitterless sampling (same-county clients are bit-identical
    f64s), counts exact.  Reports measured e2e wall including sampling."""
    import os
    import tempfile

    import jax

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import driver
    from fuzzyheavyhitters_tpu.workloads import covid

    rng = np.random.default_rng(11)
    with tempfile.TemporaryDirectory() as td:
        cpath = os.path.join(td, "county_centroids.csv")
        with open(cpath, "w") as f:
            f.write("fips_code,latitude,longitude\n")
            for i in range(n_counties):
                f.write(
                    f"{10000 + i},{25 + 25 * rng.random():.4f},"
                    f"{-120 + 50 * rng.random():.4f}\n"
                )
        t0 = time.perf_counter()
        pts = covid.sample_covid_locations(
            os.path.join(td, "absent.csv"), cpath, n, fuzz_factor=None, seed=7
        )
        k0, k1 = ibdcf.gen_l_inf_ball(pts, ball, rng, engine=_keygen_engine())
        s0, s1 = driver.make_servers(k0, k1)
        lead = driver.Leader(
            s0, s1, n_dims=2, data_len=L, f_max=2048, min_bucket=64
        )
        res = lead.run(nreqs=n, threshold=threshold)
        jax.block_until_ready(s0.frontier.states.bit)
        dt = time.perf_counter() - t0
    assert res.paths.shape[0] >= n_counties  # every hot county + ulp ball
    return {
        "covid_crawl_seconds_e2e": round(dt, 2),
        "covid_clients_per_sec": round(n / dt, 1),
        "n_clients": n,
        "data_len": L,
        "n_dims": 2,
        "hitters": int(res.paths.shape[0]),
    }


async def _bring_up_pair(cfg, port):
    """Two collector servers + leader-side clients in this process:
    s1 first (it listens on the data plane at port+11), then s0 dials —
    the reference's startup ordering (server.rs:344-354).  Returns
    (leader, c0, c1) with both servers reset."""
    import asyncio

    from fuzzyheavyhitters_tpu.protocol import rpc
    from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader

    s0 = rpc.CollectorServer(0, cfg)
    s1 = rpc.CollectorServer(1, cfg)
    t1 = asyncio.create_task(
        s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11)
    )
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(s0.start("127.0.0.1", port, "127.0.0.1", port + 11))
    c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
    c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
    await asyncio.gather(t0, t1)
    lead = RpcLeader(cfg, c0, c1)
    await asyncio.gather(c0.call("reset"), c1.call("reset"))
    return lead, c0, c1, s0, s1


def bench_secure(n=1024, L=12, port=21831, shard_nodes=4, pipeline_depth=4):
    """Secure-mode aggregate crawl: both collector servers in one process
    with the REAL 2PC data plane (secure_exchange=true), full level loop
    over localhost sockets on the default device.  End-to-end wall time.
    A level is ONE protocol round trip — ev u -> sender's whole-level
    planar message (the 1-of-2^S chosen-payload table at this 1-dim
    shape; the packed garbled batch past secure.OT2S_MAX_S) — so the
    floor is ~3 serial device<->host fetches per level (u, table,
    shares) at the reported ``device_fetch_rtt_ms``;
    ``bench_secure_device`` is the device-only number.
    Ref seam: collect.rs:419-482 inside tree_crawl.

    Round-7 shape: the HEADLINE run is WHOLE-LEVEL — every (node,
    client) wire of a level garbles/evaluates as one fused device
    program per side (``secure_whole_level``, the default), with the
    secure-kernel phase split (otext/garble/eval/b2a) captured from the
    server registries.  Three comparison legs ride along on the same
    warmed servers: the round-6 sharded+pipelined run, its sequential
    form (``pipeline_speedup`` keeps its meaning), and a GC-path
    (``ot_path="gc"``) sequential reference — and ALL results are
    asserted bit-identical before anything is reported, so the fused
    1-of-2^S path never reports numbers it didn't earn.  Compiles are
    excluded from every timing via the per-``f_bucket`` warmup verb
    (plus the persistent compile cache).  NB: the planar wire pads every GC/OT
    batch to ``gc_pallas.padded_tests`` (8192 tests), so at tiny smoke
    shapes the SHARDED leg pays the padding floor once per span and its
    ``pipeline_speedup`` reads < 1 — meaningful only at production
    shapes where spans amortize the floor."""
    import asyncio
    import dataclasses

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import rpc
    from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
    from fuzzyheavyhitters_tpu.utils.config import Config

    rng = np.random.default_rng(3)
    sites = rng.integers(0, 1 << L, size=8)
    pts = sites[rng.integers(0, 8, size=n)]
    pts_bits = (
        ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    )  # [n, 1, L] MSB-first
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine=_keygen_engine())

    cfg = Config(
        data_len=L, n_dims=1, ball_size=2, addkey_batch_size=1024,
        num_sites=8, threshold=0.05, zipf_exponent=1.03,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=64, secure_exchange=True,
        crawl_shard_nodes=shard_nodes, crawl_pipeline_depth=pipeline_depth,
    )

    async def run():
        lead, c0, c1, s0, s1 = await _bring_up_pair(cfg, port)

        async def timed_leg(leg_cfg, warm=False):
            leg = RpcLeader(leg_cfg, c0, c1)
            await asyncio.gather(c0.call("reset"), c1.call("reset"))
            await leg.upload_keys(k0, k1)
            if warm:
                # legs whose shapes the headline warmup cannot cover
                # (span-sized sharded programs, the GC path) warm their
                # own program ladder OFF the timed clock
                await leg.warmup()
            t = time.perf_counter()
            res = await leg.run(n)
            return res, time.perf_counter() - t, leg

        await lead.upload_keys(k0, k1)
        await lead.warmup()  # per-f_bucket compiles, off the clock
        res = await lead.run(n)  # warm: any residual compile/trace cost
        assert res.paths.shape[0] >= 1
        # timed HEADLINE: whole-level fused kernels (the default config)
        res_w, dt_w, _ = await timed_leg(cfg)
        # secure-kernel phase split of the timed run, BOTH servers (the
        # garbler role alternates per level, so each registry holds half
        # of every phase; reset above cleared the warm run's accounting)
        rep = s0.obs.report()
        rep1 = s1.obs.report()
        # per-level latency SLO of the timed headline run (obs.hist):
        # both servers' fixed-bucket histograms merge bucket-wise
        from fuzzyheavyhitters_tpu.obs.hist import Histogram

        lv = Histogram.merged(
            [s0.obs.hist("level_latency"), s1.obs.hist("level_latency")]
        )
        slo = {
            "level_p50_ms": round(1000 * (lv.quantile(0.5) or 0.0), 2),
            "level_p95_ms": round(1000 * (lv.quantile(0.95) or 0.0), 2),
            "level_max_ms": round(1000 * lv.max, 2),
        }
        # timed sharded+pipelined comparison (the round-6 headline);
        # the pipeline telemetry lives entirely on this leg's own fresh
        # leader registry (the whole-level legs emit none)
        pipe_cfg = dataclasses.replace(cfg, secure_whole_level=False)
        res_p, dt_p, pipe_lead = await timed_leg(pipe_cfg, warm=True)
        overlap = pipe_lead.obs.timer_seconds("pipeline_overlap")
        stalls = int(pipe_lead.obs.counter_value("pipeline_stalls"))
        # timed SEQUENTIAL comparison (PR-4 path, same warmed servers)
        res_s, dt_s, _ = await timed_leg(
            dataclasses.replace(
                cfg, crawl_shard_nodes=0, crawl_pipeline_depth=1,
                secure_whole_level=False,
            )
        )
        # GC-path sequential reference: the fused 1-of-2^S headline must
        # be bit-identical to the garbled-circuit oracle before any
        # number is reported
        res_g, dt_g, _ = await timed_leg(
            dataclasses.replace(
                cfg, ot_path="gc", crawl_shard_nodes=0,
                crawl_pipeline_depth=1,
            ),
            warm=True,
        )
        for other in (res_p, res_s, res_g):
            assert np.array_equal(res_w.counts, other.counts)
            assert np.array_equal(res_w.paths, other.paths)
        return (dt_w, dt_p, dt_s, dt_g, overlap, stalls,
                int(res_w.paths.shape[0]), rep, rep1, slo)

    (dt, dt_pipe, dt_seq, dt_gc, overlap_s, stalls, hitters, rep,
     rep1, slo) = asyncio.run(run())
    phases, ctrs = rep["phases"], rep["counters"]
    zero = {"seconds": 0.0, "total": 0}
    fss, gcot, fld = (
        round(phases.get(k, zero)["seconds"], 3)
        for k in ("fss", "gc_ot", "field")
    )
    # secure-kernel split: sum both servers' registries per phase; the
    # path taken comes from the ot_path_* counters (ot2s at this 1-dim
    # shape unless EQ_OT4 is off)
    kernel = {}
    for k in ("otext", "garble", "eval", "b2a"):
        kernel[f"phase_{k}_seconds"] = round(
            phases.get(k, zero)["seconds"]
            + rep1["phases"].get(k, zero)["seconds"], 3
        )
    n_ot2s = int(ctrs.get("ot_path_ot2s", zero)["total"])
    n_gc = int(ctrs.get("ot_path_gc", zero)["total"])
    kernel["ot_path"] = (
        "mixed" if (n_ot2s and n_gc) else ("gc" if n_gc else "ot2s")
    )
    gc_tests = int(ctrs.get("gc_tests", zero)["total"])
    # the e2e floor: every device->host fetch in the serial 2PC message
    # flow costs one of these (≈6 per level after round-4's packing)
    import jax.numpy as jnp

    a = jnp.zeros(4, jnp.uint32) + 1
    np.asarray(a)  # warm
    rtt = min(
        _time_of(lambda: np.asarray(a + i)) for i in range(3)
    )
    return {
        "secure_clients_per_sec": round(n / dt, 1),
        "secure_crawl_seconds": round(dt, 3),
        "n_clients": n,
        "data_len": L,
        "ms_per_level_e2e": round(dt / L * 1000, 2),
        "hitters": hitters,
        # the whole-level fused-kernel phase split + path of the timed
        # headline run — the ROADMAP's acceptance instrument
        "secure_kernel": kernel,
        # per-level latency quantiles (obs.hist histograms, both servers
        # merged) — the measurement campaign's SLO headline
        "slo": slo,
        # whole-level vs the round-6 sharded+pipelined path, and the
        # garbled-circuit sequential oracle everything was asserted
        # bit-identical against
        "whole_level_speedup_vs_pipelined": round(dt_pipe / dt, 2),
        "gc_reference_clients_per_sec": round(n / dt_gc, 1),
        # pipelined-vs-sequential on the same warmed servers (results
        # asserted bit-identical inside the run)
        "pipelined_clients_per_sec": round(n / dt_pipe, 1),
        "sequential_clients_per_sec": round(n / dt_seq, 1),
        "sequential_ms_per_level": round(dt_seq / L * 1000, 2),
        "pipeline_speedup": round(dt_seq / dt_pipe, 2),
        "pipeline": {
            "depth": cfg.crawl_pipeline_depth,
            "shard_nodes": cfg.crawl_shard_nodes,
            "overlap_seconds": round(overlap_s, 3),
            "stalls": stalls,
        },
        # measured equality tests of the timed run (batches are sized to
        # the live frontier bucket, not f_max)
        "gc_tests_per_level": round(gc_tests / L, 1),
        # server-0 accumulated 3-phase split (ref breakdown,
        # collect.rs:412-503); remainder vs secure_crawl_seconds is
        # control-plane + pickling + event-loop time
        "phase_fss_seconds": fss,
        "phase_gc_ot_seconds": gcot,
        "phase_field_seconds": fld,
        "device_fetch_rtt_ms": round(rtt * 1000, 1),
        # data-plane accounting from the same registry: the fetch COUNT
        # the rpc.py docstring states, measured
        "device_fetches": int(ctrs.get("device_fetches", zero)["total"]),
        "data_plane_mbytes_sent": round(
            ctrs.get("data_bytes_sent", zero)["total"] / 1e6, 2
        ),
        "data_plane_mbytes_recv": round(
            ctrs.get("data_bytes_recv", zero)["total"] / 1e6, 2
        ),
    }


def bench_radix(n=1024, L=12, port=23431, radices=(1, 2, 3)):
    """Radix-2^k level fusion sweep (``Config.crawl_radix_bits``): the
    same secure crawl at k = 1, 2, 3 bits per round trip, each k on its
    own warmed server pair.  The fused rounds widen the equality strings
    to S' = 2k (ot2s at this 1-dim shape) and cut the crawl to
    ceil(L/k) round trips — the win is the per-round fixed cost
    (control-plane verbs, device<->host fetches, OT/GC handshakes) paid
    ceil(L/k) times instead of L.

    Identity gate first, numbers second: every k's heavy-hitter counts
    AND paths are asserted bit-identical to the k=1 run before anything
    is reported, and the per-server ``rpc:{verb}`` histograms must show
    exactly ceil(L/k) crawl verbs — a sweep that cheated on either
    contract reports nothing.  Timings exclude compiles (per-radix
    warmup ladder + persistent compile cache, same policy as bench_secure).

    NB: over loopback a round trip costs ~0, while the fused ot2s
    tables grow 4^k rows per dim — so smoke shapes legitimately report
    ``speedup_vs_k1`` < 1.  The fusion wins where the tentpole aims:
    real inter-site links whose per-round fixed cost (RTT + the ~3
    serial device<->host fetches bench_secure documents) dwarfs the
    wider table, where cutting L rounds to ceil(L/k) is the headline."""
    import asyncio
    import dataclasses
    import math

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.utils.config import Config

    rng = np.random.default_rng(3)
    sites = rng.integers(0, 1 << L, size=8)
    pts = sites[rng.integers(0, 8, size=n)]
    pts_bits = (
        ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    )  # [n, 1, L] MSB-first
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine=_keygen_engine())

    base_cfg = Config(
        data_len=L, n_dims=1, ball_size=2, addkey_batch_size=1024,
        num_sites=8, threshold=0.05, zipf_exponent=1.03,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=64, secure_exchange=True,
    )

    def crawl_verbs(server):
        hists = server._default().obs.report()["hists"]
        return sum(
            hists[v]["count"]
            for v in ("rpc:tree_crawl", "rpc:tree_crawl_last")
            if v in hists
        )

    async def leg(k, leg_port):
        cfg = dataclasses.replace(
            base_cfg,
            crawl_radix_bits=k,
            server0=f"127.0.0.1:{leg_port}",
            server1=f"127.0.0.1:{leg_port + 10}",
        )
        lead, c0, c1, s0, s1 = await _bring_up_pair(cfg, leg_port)
        await lead.upload_keys(k0, k1)
        await lead.warmup()  # per-radix program ladder, off the clock
        await lead.run(n)  # warm: residual compile/trace cost
        # reset clears the warm run's verb accounting, so the histograms
        # below count the TIMED crawl's round trips alone
        await asyncio.gather(c0.call("reset"), c1.call("reset"))
        await lead.upload_keys(k0, k1)
        t = time.perf_counter()
        res = await lead.run(n)
        dt = time.perf_counter() - t
        verbs = (crawl_verbs(s0), crawl_verbs(s1))
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
        return res, dt, verbs

    async def run():
        out = {}
        for i, k in enumerate(radices):
            out[k] = await leg(k, port + 40 * i)
        return out

    legs = asyncio.run(run())
    base_res, base_dt, _ = legs[1]
    assert base_res.paths.shape[0] >= 1
    rounds_want = {k: math.ceil(L / k) for k in radices}
    sweep = {}
    for k, (res, dt, verbs) in legs.items():
        # the identity gate: a fused crawl that drifted from the k=1
        # sets/paths — or issued more round trips than it claims —
        # reports NOTHING
        assert np.array_equal(base_res.counts, res.counts), k
        assert np.array_equal(base_res.paths, res.paths), k
        assert verbs == (rounds_want[k], rounds_want[k]), (k, verbs)
        sweep[k] = {
            "crawl_seconds": round(dt, 3),
            "clients_per_sec": round(n / dt, 1),
            "round_trips": rounds_want[k],
            "ms_per_round_trip": round(dt / rounds_want[k] * 1000, 2),
            "speedup_vs_k1": round(base_dt / dt, 2),
        }
    best_k = min(legs, key=lambda k: legs[k][1])
    return {
        "n_clients": n,
        "data_len": L,
        "radix_sweep": {str(k): v for k, v in sweep.items()},
        "best_k": int(best_k),
        # bit levels crawled per round trip at the best k — the fused
        # crawl's level rate multiplier over one-bit-per-round
        "level_rate_x_k": round(L / rounds_want[best_k], 2),
        "speedup_vs_k1": sweep[best_k]["speedup_vs_k1"],
        "bit_identical": True,
    }


def bench_multichip(n=1024, L=12, port=22231, shards=(1, 2, 4, 8),
                    f_max=64, kernel_shards=(1, 2, 4, 8)):
    """Multi-chip collector servers: secure clients/sec as each server's
    client axis shards over 1/2/4/8 LOCAL data devices
    (parallel/server_mesh.py — ``Config.server_data_devices``).  Every
    sharded leg is asserted BIT-IDENTICAL to the single-device leg
    before any number is reported (sharding is a physical layout; the
    2PC transcript never changes), and the highest-shard leg's
    ``ici_reduce_seconds`` (the pre-wire psum, fetch-synced) rides the
    compact line next to ``data_shards``.  Shard counts beyond the
    visible device count (or not dividing the client batch) are
    reported as skipped, not silently dropped — on a CPU host run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the smoke
    path) all four legs run.

    KERNEL-SHARDED legs (PR 10): at the top feasible data-shard count, a
    second sweep varies ``Config.secure_kernel_shards`` over
    ``kernel_shards`` — 1 pins the gather-to-one-device kernel stage
    (the pre-PR-10 layout), higher caps run the row-sharded IKNP +
    equality kernels (parallel/kernel_shard.py).  Each leg is
    bit-identity-gated like the data legs;
    ``whole_level_speedup_vs_gathered`` is the top kernel leg's rate
    over the gathered leg's, and ``kernel_gather_seconds`` (should read
    ~0 on the sharded legs' deep levels) rides the compact line."""
    import asyncio
    import jax

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.parallel import server_mesh
    from fuzzyheavyhitters_tpu.utils.config import Config

    rng = np.random.default_rng(5)
    sites = rng.integers(0, 1 << L, size=8)
    pts = sites[rng.integers(0, 8, size=n)]
    pts_bits = (
        ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    )
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine=_keygen_engine())

    def leg_cfg(p, k, ks=0):
        return Config(
            data_len=L, n_dims=1, ball_size=2, addkey_batch_size=1024,
            num_sites=8, threshold=0.05, zipf_exponent=1.03,
            server0=f"127.0.0.1:{p}", server1=f"127.0.0.1:{p + 10}",
            distribution="zipf", f_max=f_max, secure_exchange=True,
            server_data_devices=k, secure_kernel_shards=ks,
        )

    n_devices = len(jax.devices())

    async def one_leg(k, p, ks=0):
        cfg = leg_cfg(p, k, ks)
        lead, c0, c1, s0, s1 = await _bring_up_pair(cfg, p)
        try:
            await lead.upload_keys(k0, k1)
            await lead.warmup()  # sharded program ladder, off the clock
            res = await lead.run(n)  # warm residual trace/dispatch cost
            await lead._both("reset")
            await lead.upload_keys(k0, k1)
            t = time.perf_counter()
            res = await lead.run(n)
            dt = time.perf_counter() - t
            ici = (
                s0.obs.timer_seconds("ici_reduce")
                + s1.obs.timer_seconds("ici_reduce")
            )
            st = await c0.call("status")
            # kernel_shards_max (the deepest sharding the crawl
            # engaged) comes from the status verb like every other
            # mesh-health number — the wire interface, not a reach into
            # the in-process registry
            return res, dt, ici, st.get("mesh")
        finally:
            for c in (c0, c1):
                await c.aclose()
            for s in (s0, s1):
                await s.aclose()

    rates: dict = {}
    skipped: dict = {}
    base_res = None
    top = (1, 0.0, None)  # (shards, ici_reduce_s, mesh status)
    for i, k in enumerate(shards):
        if k > n_devices:
            skipped[str(k)] = "devices"
            continue
        if server_mesh._largest_divisor_leq(n, k) != k:
            skipped[str(k)] = "batch"
            continue
        # the data-shard sweep pins the GATHERED kernel stage (kernel
        # cap 1) so its legs measure exactly what PR 8 measured; the
        # kernel sweep below owns the sharded-kernel comparison
        res, dt, ici, mesh_st = asyncio.run(one_leg(k, port + 40 * i, ks=1))
        rates[str(k)] = round(n / dt, 1)
        if base_res is None:
            base_res = res
        else:
            # gate: a sharded leg that is not bit-identical to the
            # single-device leg reports nothing
            assert np.array_equal(base_res.counts, res.counts)
            assert np.array_equal(base_res.paths, res.paths)
        if k >= top[0]:
            top = (k, ici, mesh_st)
    # kernel-sharded sweep at the top feasible data-shard count: vary
    # the secure_kernel_shards cap, 1 = the gathered baseline
    kernel_rates: dict = {}
    kernel_skipped: dict = {}
    k_top_status = None
    k_engaged = None
    kg_seconds = None
    data_top = top[0]
    for j, s in enumerate(kernel_shards):
        if base_res is None or data_top < 2:
            kernel_skipped[str(s)] = "devices"
            continue
        if s > data_top:
            kernel_skipped[str(s)] = "devices"
            continue
        if s == 1 and str(data_top) in rates:
            # the gathered baseline IS the data sweep's top leg (the
            # data legs pin kernel cap 1) — reuse its rate instead of
            # re-running an identical warmed server pair
            kernel_rates["1"] = rates[str(data_top)]
            continue
        res, dt, ici, mesh_st = asyncio.run(
            one_leg(data_top, port + 2000 + 40 * j, ks=s)
        )
        assert np.array_equal(base_res.counts, res.counts)
        assert np.array_equal(base_res.paths, res.paths)
        kernel_rates[str(s)] = round(n / dt, 1)
        if mesh_st is not None:
            k_top_status = mesh_st
            if s > 1:
                k_engaged = mesh_st.get("kernel_shards_max")
                kg_seconds = mesh_st.get("kernel_gather_seconds")
    speedup = None
    if len(kernel_rates) > 1 and kernel_rates.get("1"):
        best = max(
            v for s, v in kernel_rates.items() if s != "1"
        )
        speedup = round(best / kernel_rates["1"], 3)
    return {
        "bit_identical": base_res is not None and len(rates) > 1,
        "data_shards": top[0],
        "ici_reduce_seconds": round(top[1], 3),
        "secure_clients_per_sec": rates,
        "skipped_shards": skipped,
        # kernel-sharded legs (bit-identity-gated like the data legs)
        "kernel_shards": k_engaged,
        "kernel_clients_per_sec": kernel_rates,
        "kernel_gather_seconds": kg_seconds,
        "whole_level_speedup_vs_gathered": speedup,
        "kernel_skipped": kernel_skipped,
        "n_clients": n,
        "data_len": L,
        "n_devices": n_devices,
        "mesh_status": k_top_status or top[2],
    }


def bench_sketch(n=1024, L=12, port=23031, shards=(1, 2, 4, 8),
                 data_devices=8, secure=True):
    """Malicious-secure sketch verification in the fast lane
    (parallel/sketch_shard.py): the headline is
    ``malicious_overhead_vs_semi_honest`` — one crawl WITH the sketch
    gates (MAC'd payload DPFs verified per level, the device-resident
    fused verify) over the identical crawl WITHOUT them, same config,
    same warmed servers per leg.  A sharded sweep varies
    ``Config.sketch_shards`` over ``shards`` on an
    ``data_devices``-wide data mesh; every sharded leg is gated TWICE
    before anything is reported:

    - DIRECTLY: the trusted challenge stream (r + rand rows, by CTR
      seek) and the cor-share wire bytes at shard count k are asserted
      byte-identical to the single fused program's, per field — the
      check that catches a seek bug e2e results cannot (honest clients
      pass under ANY challenge, so result equality alone is blind to a
      perturbed stream);
    - E2E: the sharded leg's heavy hitters, paths, AND the per-client
      liveness vector are asserted bit-identical to the unsharded
      malicious leg's.

    Clients are honest here (the overhead number should price the
    checks, not a cheater's exclusion); cheater-detection parity is
    tier-1's job (tests/test_sketch_shard.py)."""
    import asyncio

    import jax
    import jax.numpy as jnp

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
    from fuzzyheavyhitters_tpu.parallel import server_mesh, sketch_shard
    from fuzzyheavyhitters_tpu.protocol import mpc, sketch as sketchmod
    from fuzzyheavyhitters_tpu.utils.config import Config

    rng = np.random.default_rng(9)
    sites = rng.integers(0, 1 << L, size=8)
    pts = sites[rng.integers(0, 8, size=n)]
    pts_bits = (
        ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    )  # [n, 1, L] MSB-first
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine=_keygen_engine())
    seeds = rng.integers(0, 2**32, size=(n, 1, 2, 4), dtype=np.uint32)
    cseed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    sk0, sk1 = sketchmod.gen(seeds, pts_bits, FE62, F255, cseed)

    def leg_cfg(p, sketch_k):
        return Config(
            data_len=L, n_dims=1, ball_size=2, addkey_batch_size=1024,
            num_sites=8, threshold=0.05, zipf_exponent=1.03,
            server0=f"127.0.0.1:{p}", server1=f"127.0.0.1:{p + 10}",
            distribution="zipf", f_max=64, secure_exchange=secure,
            malicious=True, server_data_devices=data_devices,
            sketch_shards=sketch_k,
        )

    n_devices = len(jax.devices())

    def direct_gate(k) -> None:
        """Challenge stream + cor wire at shard count k vs the single
        fused program — byte-identical or the leg reports nothing."""
        devs = tuple(jax.local_devices()[:k])
        ss = sketch_shard.bind(devs, n, 1, k)
        assert ss is not None and ss.k == k, (k, ss)
        m, lvl = 8, 3
        for field in (FE62, F255):
            r_ref, rands_ref = sketchmod.shared_r_stream(
                field, cseed, lvl, m, n
            )
            r, ra = sketch_shard.stream_parts(ss, field, cseed, lvl, m, n, 1)
            assert np.array_equal(np.asarray(r_ref), r)
            assert np.array_equal(np.asarray(rands_ref), ra)
            w = 8 if field.limb_shape else 4
            pairs = field.sample(jnp.asarray(rng.integers(
                0, 2**32, size=(m, n, 1, 2, w), dtype=np.uint32
            )))
            trip, _ = mpc.gen_triples(field, (n, 1, mpc.CHECKS), cseed)
            mk = field.sample(jnp.asarray(rng.integers(
                0, 2**32, size=(n, w), dtype=np.uint32
            )))
            mk2 = field.mul(mk, mk)
            cor_1, _ = sketch_shard.cor_state(
                None, field, pairs, trip, mk, mk2, cseed, lvl
            )
            cor_k, _ = sketch_shard.cor_state(
                ss, field, pairs, trip, mk, mk2, cseed, lvl
            )
            assert np.array_equal(
                sketch_shard.wire(cor_1), sketch_shard.wire(cor_k)
            ), (field.__name__, k)

    async def one_leg(p, sketch_k, with_sketch=True):
        cfg = leg_cfg(p, sketch_k)
        lead, c0, c1, s0, s1 = await _bring_up_pair(cfg, p)
        try:
            sks = (sk0, sk1) if with_sketch else (None, None)
            await lead.upload_keys(k0, k1, *sks)
            await lead.warmup()  # fused verify ladder, off the clock
            res = await lead.run(n)  # warm residual trace/dispatch cost
            await lead._both("reset")
            await lead.upload_keys(k0, k1, *sks)
            t = time.perf_counter()
            res = await lead.run(n)
            dt = time.perf_counter() - t
            alive = None if not with_sketch else s0.alive_keys.copy()
            sketch_s = (
                s0.obs.timer_seconds("sketch")
                + s1.obs.timer_seconds("sketch")
            )
            st = await c0.call("status")
            return res, dt, alive, sketch_s, (st.get("mesh") or {})
        finally:
            for c in (c0, c1):
                await c.aclose()
            for s in (s0, s1):
                await s.aclose()

    # semi-honest reference: the identical crawl without the sketch
    # gates (same shapes, same warmed servers-per-leg discipline)
    res_semi, dt_semi, _, _, _ = asyncio.run(one_leg(port, 1, False))
    rates: dict = {}
    skipped: dict = {}
    base_res = None
    base_alive = None
    top = (0, None, None)  # (shards, dt, verify seconds)
    for i, k in enumerate(shards):
        if k > 1 and (
            k > n_devices
            or server_mesh._largest_divisor_leq(n, k) != k
        ):
            skipped[str(k)] = "devices" if k > n_devices else "batch"
            continue
        if k > 1:
            direct_gate(k)
        res, dt, alive, sketch_s, mesh_st = asyncio.run(
            one_leg(port + 100 + 40 * i, k)
        )
        if k > 1 and (mesh_st.get("sketch_shards") or 1) != k:
            # the server's mesh could not hold k shards (fewer visible
            # devices than requested): report it skipped, never as a
            # sharded number it didn't earn
            skipped[str(k)] = "devices"
            continue
        rates[str(k)] = round(n / dt, 1)
        if base_res is None:
            base_res, base_alive = res, alive
        else:
            # e2e gate: hitters, paths, AND liveness bit-identical to
            # the unsharded malicious leg
            assert np.array_equal(base_res.counts, res.counts)
            assert np.array_equal(base_res.paths, res.paths)
            assert np.array_equal(base_alive, alive)
        if k >= top[0]:
            top = (k, dt, sketch_s)
    dt_mal = top[1]
    if base_res is not None:
        # honest clients: the malicious legs' outputs must equal the
        # semi-honest reference's (the checks gate liveness, they never
        # perturb counts), and every client must survive its checks
        assert np.array_equal(base_res.counts, res_semi.counts)
        assert np.array_equal(base_res.paths, res_semi.paths)
        assert base_alive is not None and bool(base_alive.all())
    return {
        "bit_identical": base_res is not None and len(rates) >= 1,
        "malicious_overhead_vs_semi_honest": (
            None if dt_mal is None else round(dt_mal / dt_semi, 3)
        ),
        "sketch_clients_per_sec": (
            None if dt_mal is None else round(n / dt_mal, 1)
        ),
        "semi_honest_clients_per_sec": round(n / dt_semi, 1),
        "sketch_shards": top[0],
        "clients_per_sec_by_shards": rates,
        "verify_seconds": (
            None if top[2] is None else round(top[2], 3)
        ),
        "skipped_shards": skipped,
        "secure_exchange": bool(secure),
        "n_clients": n,
        "data_len": L,
        "n_devices": n_devices,
    }


def bench_secure_device(n=65536, L=64, f_bucket=4, with_l512=True):
    """Device-resident secure-crawl measurement at FLAGSHIP shape: the
    WHOLE per-level 2PC — both parties' expand, label extension, garbling,
    evaluation, output-label b2a (the fused flow the socket path ships),
    alive-gated share sums — as ONE jitted program on one chip.

    This is the 1-chip stand-in for the 2-chip mesh deployment
    (parallel/mesh.py runs the same math with the messages as ``ppermute``
    transfers): it measures what the 2PC costs where the north star runs
    it — chips adjacent to the servers — while ``bench_secure`` measures
    the socket e2e, which also pays the host's per-level device<->host
    fetches and the wire.  Shape: n >= 65k
    clients, L >= 64, the steady zipf frontier bucket; ``with_l512`` adds
    one level on data_len=512 keys (per-level 2PC cost is L-independent —
    the measurement demonstrates it).  GC-table HBM bytes are reported
    for the garbled batch + payload ciphertexts."""
    import jax
    import jax.numpy as jnp

    from fuzzyheavyhitters_tpu.ops import baseot, gc, ibdcf, otext
    from fuzzyheavyhitters_tpu.ops import prg as prgmod
    from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
    from fuzzyheavyhitters_tpu.protocol import collect, secure

    rng = np.random.default_rng(3)
    d = 1
    C, S = 1 << d, 2 * d
    B = f_bucket * C * n  # headline-shape test count (gc_bytes, report)

    s_bits = otext.fresh_s_bits()
    seeds0, seeds1, chosen = baseot.exchange(s_bits)
    s_bits_d = jnp.asarray(s_bits.astype(np.uint32))
    sm_snd = jnp.asarray(chosen.astype(np.uint32))
    sm_rcv = jnp.asarray(seeds0.astype(np.uint32))
    sa_rcv = jnp.asarray(seeds1.astype(np.uint32))
    gseed = jnp.asarray(np.frombuffer(b"bench-gc-seed..!", "<u4").copy())
    bseed = jnp.asarray(np.frombuffer(b"bench-b2aseed.!!", "<u4").copy())

    def make_keys(data_len):
        sites = rng.integers(0, 2, size=(8, 1, data_len)).astype(bool)
        pts_bits = sites[rng.integers(0, 8, size=n)]
        k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine=_keygen_engine())
        # steady-state frontier: f_bucket slots (root states replicated;
        # the 2PC math is state-value-independent), all nodes+keys live
        f0 = collect.tree_init(k0, f_bucket)._replace(alive=jnp.ones(f_bucket, bool))
        f1 = collect.tree_init(k1, f_bucket)._replace(alive=jnp.ones(f_bucket, bool))
        return k0, k1, f0, f1

    k0, k1, f0, f1 = make_keys(L)
    alive_keys = jnp.ones(n, bool)

    def level_fn(field, fb=f_bucket, eq_ot4=None):
        limb = field.limb_shape
        W = secure.payload_words(field)
        B = fb * C * n
        m = B * S
        w = jnp.asarray(
            secure.alive_weight(np.ones(fb, bool), np.ones(n, bool), C)
        )
        if eq_ot4 is None:
            eq_ot4 = secure._ot4_use(S)

        @jax.jit
        def run(keys0, fr0, keys1, fr1, lvl):
            p0, _ = collect.expand_share_bits(keys0, fr0, lvl, want_children=False)
            p1, _ = collect.expand_share_bits(keys1, fr1, lvl, want_children=False)
            flat0 = secure.child_strings(p0, d).reshape(B, S)  # garbler x
            flat1 = secure.child_strings(p1, d).reshape(B, S)  # evaluator y
            off = jnp.uint32(0)
            u, t_rows = otext._receiver_extend(
                sm_rcv, sa_rcv, flat1.reshape(m), off, m
            )
            q = otext._sender_extend(sm_snd, s_bits_d, u, off, m)
            s_block = otext.pack_bits(s_bits_d)
            r_words = prgmod.stream_words(bseed, B * W).reshape(B, W)
            r0 = field.sample(r_words)
            r1 = field.add(r0, field.from_int(1))
            w0, w1 = secure.field_to_words(field, r0), secure.field_to_words(field, r1)
            if eq_ot4:
                # S = 2 fast path: 1-of-4 chosen-payload OT, no circuit
                cts4 = secure.ot4_encrypt(
                    q.reshape(B, S, 4), s_block, flat0, w1, w0, W, 0
                )
                pay = secure.ot4_decrypt(
                    t_rows.reshape(B, S, 4), flat1, cts4, W, 0
                )
            else:
                # GC + fused output-label b2a (the parity path's math)
                batch, cts, _mask = gc.garble_equality_payload(
                    s_block, q.reshape(B, S, 4), gseed, flat0, w1, w0, W, 0
                )
                _, pay = gc.eval_equality_payload(
                    batch, t_rows.reshape(B, S, 4), cts, W, 0
                )
            v1 = secure.words_to_field(field, pay)
            sh0 = secure.node_share_sums(
                field, r1.reshape((fb, C, n) + limb), w
            )
            sh1 = secure.node_share_sums(
                field, v1.reshape((fb, C, n) + limb), w
            )
            return sh0, sh1

        return run

    def _lvl_seconds(run_fn, *args, iters=32):
        """Steady-state s/level: one dependent fetch over the first output
        leaf of every queued launch (see _steady_state_seconds)."""
        first = lambda o: jnp.ravel(
            jax.tree_util.tree_leaves(o)[0]
        )[0].astype(jnp.uint64)
        return _steady_state_seconds(
            lambda: run_fn(*args),
            lambda outs: int(sum(first(o) for o in outs)),
            lambda o: int(first(o)),
            iters=iters,
        )

    # engine A/B, non-default engines first and the default LAST so the
    # headline numbers come from the default engine's run (the crawl
    # bench's convention — only back-to-back comparisons mean anything on
    # the shared chip): the GC+fused-b2a path (the reference-parity
    # protocol shape, S-general) vs the S = 2 1-of-4-OT fast path
    # (secure.EQ_OT4, the production default for 1-dim crawls)
    from fuzzyheavyhitters_tpu.ops import gc as gcmod

    best_xla_gc = None
    best_gc_path = None
    if gcmod._pallas_engine():  # GC path on the XLA gc engine
        gcmod.GC_PALLAS = False
        try:
            run_x = level_fn(FE62, eq_ot4=False)
            run_x(k0, f0, k1, f1, 0)  # warm/compile
            best_xla_gc = _lvl_seconds(run_x, k0, f0, k1, f1, 0)
        finally:
            gcmod.GC_PALLAS = True
    if secure._ot4_use(S):  # GC path on its default engine (the ot4
        # headline's comparison point; identical to the headline otherwise)
        run_g = level_fn(FE62, eq_ot4=False)
        run_g(k0, f0, k1, f1, 0)  # warm/compile
        best_gc_path = _lvl_seconds(run_g, k0, f0, k1, f1, 0)

    results = {}
    for name, field in (("fe62", FE62), ("f255", F255)):
        run = level_fn(field)
        # correctness pin: reconstructed counts == trusted compare
        sh0, sh1 = run(k0, f0, k1, f1, 0)
        v = np.asarray(field.canon(field.sub(sh0, sh1)))
        counts = v[..., 0] if field is F255 else v
        masks = collect.pattern_masks(d)
        p0, _ = collect.expand_share_bits(k0, f0, 0, want_children=False)
        p1, _ = collect.expand_share_bits(k1, f1, 0, want_children=False)
        want = np.asarray(collect.counts_by_pattern(
            p0, p1, jnp.asarray(masks), alive_keys, jnp.ones(f_bucket, bool)
        ))
        assert np.array_equal(counts.astype(np.uint64), want.astype(np.uint64))
        results[name] = _lvl_seconds(run, k0, f0, k1, f1, 0)
    out_extra = {}
    if with_l512:
        k0b, k1b, f0b, f1b = make_keys(512)
        run = level_fn(FE62)
        run(k0b, f0b, k1b, f1b, 100)  # warm/compile the L=512 key shapes
        best512 = _lvl_seconds(run, k0b, f0b, k1b, f1b, 100, iters=16)
        out_extra["secure_device_ms_per_level_fe62_L512_keys"] = round(
            best512 * 1000, 3
        )
    # trusted-mode comparator at the SAME shape (both expands + plaintext
    # pattern counts — what secure mode replaces with GC+OT), so the
    # secure-vs-trusted cost ratio is explicit and same-run
    masks = jnp.asarray(collect.pattern_masks(d))
    a_keys = jnp.ones(n, bool)
    a_nodes = jnp.ones(f_bucket, bool)

    @jax.jit
    def trusted_level(keys0, fr0, keys1, fr1, lvl):
        p0, _ = collect.expand_share_bits(keys0, fr0, lvl, want_children=False)
        p1, _ = collect.expand_share_bits(keys1, fr1, lvl, want_children=False)
        return collect.counts_by_pattern(p0, p1, masks, a_keys, a_nodes)

    trusted_level(k0, f0, k1, f1, 0)
    best_trusted = _lvl_seconds(trusted_level, k0, f0, k1, f1, 0)
    # Contention guard: the shared chip occasionally hits multi-minute
    # windows where memory-heavy programs run ~15x slow (observed: the
    # same secure level measuring 19 ms and 294 ms an hour apart while
    # the small hash-margin garble held steady).  The design floor of
    # secure/trusted is ~3x (GC path ~4x); a ratio far above it flags
    # such a window, so wait it out once and re-measure every affected
    # side, reporting that the retry happened — min-of-trials inside one
    # window can't see this.  The speedup ratios are computed AFTER this
    # guard so they always compare the numbers actually reported.
    def _contended(x):
        return x is not None and x / best_trusted > 8

    if (_contended(results["fe62"]) or _contended(results["f255"])
            or _contended(best_gc_path) or _contended(best_xla_gc)):
        time.sleep(75)
        run_r = level_fn(FE62)
        run_r(k0, f0, k1, f1, 0)
        results["fe62"] = min(results["fe62"],
                              _lvl_seconds(run_r, k0, f0, k1, f1, 0))
        run_r5 = level_fn(F255)
        run_r5(k0, f0, k1, f1, 0)
        results["f255"] = min(results["f255"],
                              _lvl_seconds(run_r5, k0, f0, k1, f1, 0))
        if best_gc_path is not None:
            run_g2 = level_fn(FE62, eq_ot4=False)
            run_g2(k0, f0, k1, f1, 0)
            best_gc_path = min(best_gc_path,
                               _lvl_seconds(run_g2, k0, f0, k1, f1, 0))
        if best_xla_gc is not None:
            # run_x is still in scope and already compiled (the GC engine
            # was dispatched at ITS trace time, so no flag toggle needed)
            best_xla_gc = min(best_xla_gc,
                              _lvl_seconds(run_x, k0, f0, k1, f1, 0))
        best_trusted = min(best_trusted,
                           _lvl_seconds(trusted_level, k0, f0, k1, f1, 0))
        out_extra["contention_retry"] = True
    out_extra["trusted_same_shape_ms_per_level"] = round(best_trusted * 1000, 3)
    out_extra["secure_over_trusted_ratio"] = round(
        results["fe62"] / best_trusted, 2
    )
    if best_gc_path is not None:
        out_extra["secure_device_ms_per_level_fe62_gc_path"] = round(
            best_gc_path * 1000, 3
        )
        out_extra["ot4_speedup_vs_gc_path"] = round(
            best_gc_path / results["fe62"], 2
        )
    if best_xla_gc is not None:
        out_extra["secure_device_ms_per_level_fe62_xla_gc"] = round(
            best_xla_gc * 1000, 3
        )
        out_extra["gc_engine_speedup_vs_xla"] = round(
            best_xla_gc / (best_gc_path if best_gc_path is not None
                           else results["fe62"]), 2
        )

    # second point at DOUBLE the bucket (same keys/clients, 2x the 2PC
    # work): splits the per-launch dispatch overhead from the marginal
    # per-test cost, as in bench_crawl's two-point fit
    f0b = collect.tree_init(k0, 2 * f_bucket)._replace(
        alive=jnp.ones(2 * f_bucket, bool)
    )
    f1b = collect.tree_init(k1, 2 * f_bucket)._replace(
        alive=jnp.ones(2 * f_bucket, bool)
    )
    run2 = level_fn(FE62, fb=2 * f_bucket)
    run2(k0, f0b, k1, f1b, 0)
    # same iters as the fb=f_bucket point (RTT amortizes identically)
    best2 = _lvl_seconds(run2, k0, f0b, k1, f1b, 0)
    # raw fit (may go negative under chip noise — that honestly flags a
    # degenerate measurement rather than reporting extra work as free)
    marg = best2 - results["fe62"]
    out_extra["secure_device_ms_per_level_fe62_2x_bucket"] = round(
        best2 * 1000, 3
    )
    out_extra["secure_device_marginal_ns_per_test"] = round(
        marg / (f_bucket * C * n) * 1e9, 2
    )

    total = results["fe62"] * (L - 1) + results["f255"]
    # data-plane batch resident per level (FE62 words): the 1-of-4 payload
    # table on the fast path, garbled batch + payload ciphertexts on GC
    if secure._ot4_use(S):
        gc_bytes = B * 4 * 4 * 4  # cts uint32[4, B, W=4]
    else:
        gc_bytes = B * ((S - 1) * 2 * 16 + S * 16 + 4 + 2 * 4 * 4)
    return {
        "secure_device_clients_per_sec": round(n / total, 1),
        "secure_device_ms_per_level_fe62": round(results["fe62"] * 1000, 3),
        "secure_device_ms_per_level_f255": round(results["f255"] * 1000, 3),
        "secure_device_crawl_seconds": round(total, 3),
        "n_clients": n,
        "data_len": L,
        "f_bucket": f_bucket,
        "gc_tests_per_level": B,
        "gc_batch_mbytes_per_level_fe62": round(gc_bytes / 1e6, 1),
        **out_extra,
    }


def bench_hbm(n=196608, L=512, levels=3, f_max=64):
    """HBM scale validation: ACTUALLY allocate the L=512 key batch for the
    largest N this bench holds on one chip (both servers' batches — the
    1-chip driver shape, so one server's real footprint is half), run 3
    crawl levels on it, and report measured bytes — replacing the round-3
    plan that was arithmetic, not a measurement."""
    import jax
    import jax.numpy as jnp

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import driver

    rng = np.random.default_rng(0)
    sites = rng.integers(0, 2, size=(4, 1, L)).astype(bool)
    pts_bits = sites[rng.integers(0, 4, size=n)]
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine=_keygen_engine())
    jax.block_until_ready(k0.cw_seed)
    key_bytes = sum(
        leaf.nbytes for k in (k0, k1) for leaf in jax.tree.leaves(k)
    )
    per_client_per_server = key_bytes / 2 / n
    s0, s1 = driver.make_servers(k0, k1)
    lead = driver.Leader(s0, s1, n_dims=1, data_len=L, f_max=f_max)
    lead.tree_init()
    for lvl in range(levels):  # warm (compiles the small-bucket shapes)
        lead.run_level(lvl, nreqs=n, threshold=0.05)
    lead.tree_init()
    t0 = time.perf_counter()
    for lvl in range(levels):
        n_alive = lead.run_level(lvl, nreqs=n, threshold=0.05)
    dt = time.perf_counter() - t0
    assert n_alive >= 1
    # one v5e chip has 16 GB; leave 15% headroom for transients
    max_n_one_server = int(16e9 * 0.85 / per_client_per_server)
    return {
        "n_clients_allocated": n,
        "levels_run": levels,
        "key_gbytes_on_chip_both_servers": round(key_bytes / 1e9, 2),
        "measured_key_bytes_per_client_per_server": round(
            per_client_per_server, 1
        ),
        "ms_per_level_e2e": round(dt / levels * 1000, 2),
        "projected_max_clients_one_chip_16gb": max_n_one_server,
        "chips_for_1m_clients_keys": round(1e6 / max_n_one_server, 2),
    }


def bench_hash_margin(B=131072, S=2):
    """Measured cost of the ChaCha round count in the GC hash role (the
    correlation-robust hash of garbling; ops/prg.py N_ROUNDS note): one
    garble of a [B, S] equality batch at 8 / 12 / 20 rounds."""
    import secrets as pysecrets

    import jax
    import jax.numpy as jnp

    from fuzzyheavyhitters_tpu.ops import gc, prg

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2, size=(B, S)).astype(bool))
    y0 = jnp.asarray(rng.integers(0, 2**32, size=(B, S, 4), dtype=np.uint32))
    s_block = jnp.asarray(
        rng.integers(0, 2**32, size=4, dtype=np.uint32)
    )
    seed = jnp.asarray(np.frombuffer(pysecrets.token_bytes(16), "<u4").copy())
    out = {"gc_batch": B * S}
    for rounds in (8, 12, 20):
        prg.N_ROUNDS = rounds
        jax.clear_caches()  # N_ROUNDS is read at trace time
        best = _steady_state_seconds(
            lambda: gc.garble_equality_delta(s_block, y0, seed, x)[0].tables,
            lambda outs: int(sum(jnp.sum(o[0, 0]) for o in outs)),
            lambda o: int(jnp.sum(o[0, 0])),
            iters=32,
        )
        out[f"garble_ms_rounds_{rounds}"] = round(best * 1000, 3)
    prg.N_ROUNDS = 8
    jax.clear_caches()
    return out


def bench_upload(n=1_000_000, L=16, batch=4000, port=21731):
    """1M-key ingest benchmark: leader -> two servers over localhost TCP
    with the ROLLING upload window (leader_rpc.upload_keys; ref:
    leader.rs:340-364's 1000 in-flight batches).  Host-side only —
    add_keys appends buffers; the device sees keys once at tree_init."""
    import asyncio

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import rpc
    from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
    from fuzzyheavyhitters_tpu.utils.config import Config

    rng = np.random.default_rng(1)
    alpha = rng.integers(0, 2, size=(n, 1, 2, L)).astype(bool)
    seeds = rng.integers(0, 2**32, size=(n, 1, 2, 2, 4), dtype=np.uint32)
    side = np.broadcast_to(np.array([True, False]), (n, 1, 2))
    # HOST keygen on purpose: this bench measures control-plane ingest, and
    # the keys must be host-resident contiguous buffers (client-axis chunk
    # slices then pickle zero-copy).  Measured: chip keygen + device fetch
    # yields NON-contiguous leaves whose chunks copy on every pickle
    # (368 MB/s vs 2.8 GB/s), and at L=16 the fetch alone dwarfs host
    # keygen time.
    k0, k1 = ibdcf.gen_pair_np(seeds, alpha, side)

    cfg = Config(
        data_len=L, n_dims=1, ball_size=1, addkey_batch_size=batch,
        num_sites=4, threshold=0.1, zipf_exponent=1.03,
        server0=f"127.0.0.1:{port}", server1=f"127.0.0.1:{port + 10}",
        distribution="zipf", f_max=32,
    )

    async def run():
        lead, c0, c1, _, _ = await _bring_up_pair(cfg, port)
        t = time.perf_counter()
        await lead.upload_keys(k0, k1)
        return time.perf_counter() - t

    dt = asyncio.run(run())
    # _key_wire_bytes slices only the client axis, so for these [n, 1, 2]
    # interval batches it already covers both sides = one server's payload
    per_key_bytes = _key_wire_bytes(k0)
    return {
        "upload_keys_per_sec": round(n / dt, 1),
        "upload_seconds": round(dt, 3),
        "n_keys": n,
        "addkey_batch_size": batch,
        "approx_mb_per_sec": round(n * per_key_bytes / dt / 1e6, 1),
    }


def bench_ingest(n=65536, L=12, chunk=256, port=21931, threshold=0.05):
    """Streaming front-door benchmark (ROADMAP "Streaming ingestion",
    ≥ 100k keys/sec acceptance): clients submit key chunks continuously
    through the admission-controlled ``submit_keys`` verb into tumbling
    windows; window 0 is sealed and crawled while window 1 keeps
    ingesting CONCURRENTLY (``submit_keys`` bypasses the servers' verb
    lock).  Reports the sustained admission rate for both phases — pure
    ingest and ingest-during-crawl — plus the windowed crawl seconds,
    and asserts the windowed window-0 result BIT-IDENTICAL to a batch
    (``upload_keys`` + ``run``) crawl over the same admitted key set
    before reporting anything.  Host-side ingest: keys stream as numpy
    buffers; the device sees them once at each ``window_load``."""
    import asyncio

    from fuzzyheavyhitters_tpu.obs import report as obsreport
    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol.leader_rpc import WindowedIngest
    from fuzzyheavyhitters_tpu.utils.config import Config

    rng = np.random.default_rng(5)
    sites = rng.integers(0, 1 << L, size=8)
    pts = sites[rng.integers(0, 8, size=n)]
    pts_bits = (
        ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    )  # [n, 1, L] MSB-first
    # host NumPy keygen on purpose (like bench_upload): ingest is a
    # control-plane path and the chunks must be host-contiguous buffers
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")

    def mkcfg(p):
        return Config(
            data_len=L, n_dims=1, ball_size=1, addkey_batch_size=1024,
            num_sites=8, threshold=threshold, zipf_exponent=1.03,
            server0=f"127.0.0.1:{p}", server1=f"127.0.0.1:{p + 10}",
            distribution="zipf", f_max=64,
            ingest_window_keys=max(n, 1 << 20),
        )

    half = n // 2

    def chunks(lo, hi):
        for i, c0_lo in enumerate(range(lo, hi, chunk)):
            sl = slice(c0_lo, min(c0_lo + chunk, hi))
            yield (
                f"site{i % 8}",
                tuple(np.asarray(x)[sl] for x in k0),
                tuple(np.asarray(x)[sl] for x in k1),
            )

    out = {}

    async def run():
        lead, c0, c1, s0, s1 = await _bring_up_pair(mkcfg(port), port)
        wi = WindowedIngest(lead, checkpoint=False)
        # window 0: pure ingest throughput
        t0 = time.perf_counter()
        for cid, a, b in chunks(0, half):
            await wi.submit(cid, a, b)
        dt_ingest = time.perf_counter() - t0
        stats0 = await wi.seal_window()
        # window 1 ingests WHILE window 0's crawl runs
        async def pump():
            t = time.perf_counter()
            for cid, a, b in chunks(half, n):
                await wi.submit(cid, a, b)
            return time.perf_counter() - t

        t_crawl = time.perf_counter()
        crawl_task = asyncio.create_task(wi.crawl_window(0))
        dt_concurrent = await pump()
        res0 = await crawl_task
        dt_crawl = time.perf_counter() - t_crawl
        stats1 = await wi.seal_window()
        rep = obsreport.run_report([wi.obs])
        ing = rep.get("ingest") or {}
        out["ingest_keys_per_sec"] = round(half / dt_ingest, 1)
        out["concurrent_keys_per_sec"] = round((n - half) / dt_concurrent, 1)
        out["window_crawl_seconds"] = round(dt_crawl, 3)
        out["windows"] = int(ing.get("windows", 2))
        out["admitted"] = int(ing.get("admitted", 0))
        out["shed"] = int(stats0["shed_keys"]) + int(stats1["shed_keys"])
        out["rejected"] = int(ing.get("rejected", 0))
        out["n_keys"] = n
        out["chunk_keys"] = chunk
        out["report_ingest"] = ing
        # SLO quantiles of the streaming run (obs.hist): the window's
        # seal-to-hitters latency (driver clock), the e2e admit latency
        # (gate + mirror + backoffs), and the servers' per-level crawl
        # latency — the always-on dashboard's first-class metrics
        from fuzzyheavyhitters_tpu.obs.hist import Histogram

        sh = wi.obs.hist("seal_to_hitters") or Histogram()
        adm = wi.obs.hist("ingest_admit") or Histogram()
        lv = Histogram.merged(
            [s0.obs.hist("level_latency"), s1.obs.hist("level_latency")]
        )
        out["slo"] = {
            "seal_to_hitters_p50_s": round(sh.quantile(0.5) or 0.0, 4),
            "seal_to_hitters_p95_s": round(sh.quantile(0.95) or 0.0, 4),
            "admit_p95_ms": round(1000 * (adm.quantile(0.95) or 0.0), 3),
            "level_p95_ms": round(1000 * (lv.quantile(0.95) or 0.0), 2),
        }
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
        return res0

    async def batch():
        from fuzzyheavyhitters_tpu.ops.ibdcf import IbDcfKeyBatch

        lead, c0, c1, s0, s1 = await _bring_up_pair(mkcfg(port + 40), port + 40)
        bk0 = IbDcfKeyBatch(*(np.asarray(x)[:half] for x in k0))
        bk1 = IbDcfKeyBatch(*(np.asarray(x)[:half] for x in k1))
        await lead.upload_keys(bk0, bk1)
        res = await lead.run(half)
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()
        return res

    res_windowed = asyncio.run(run())
    res_batch = asyncio.run(batch())
    # the number is only reported once the windowed path EARNED it
    if not (
        np.array_equal(res_windowed.counts, res_batch.counts)
        and np.array_equal(res_windowed.paths, res_batch.paths)
    ):
        raise AssertionError(
            "windowed window-0 crawl diverged from the batch crawl over "
            "the same admitted keys"
        )
    out["bit_identical_vs_batch"] = True
    return out


def bench_multitenant(n=1024, L=10, port=22531, tenant_counts=(1, 2, 4),
                      threshold=0.05):
    """Multi-tenant collection sessions (protocol/sessions.py): N
    concurrent collections on ONE server pair, each its own session
    (own frontier, own OT streams, own ingest gate), device work
    interleaved by the TenantScheduler.  Reports aggregate SECURE
    clients/sec at 1/2/4 concurrent collections vs the solo baseline,
    plus the stall-fill ratio (device turns that ran while another
    tenant waited on the GC/OT wire — the ``pipeline_stalls`` gap a
    second tenant fills).  Every tenant's heavy-hitter set is asserted
    BIT-IDENTICAL to its solo single-session run before anything is
    reported."""
    import asyncio

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import rpc
    from fuzzyheavyhitters_tpu.protocol.leader_rpc import (
        MultiCollectionDriver,
    )
    from fuzzyheavyhitters_tpu.utils.config import Config

    def mkcfg(p):
        return Config(
            data_len=L, n_dims=1, ball_size=1, addkey_batch_size=2048,
            num_sites=8, threshold=threshold, zipf_exponent=1.03,
            server0=f"127.0.0.1:{p}", server1=f"127.0.0.1:{p + 10}",
            distribution="zipf", f_max=64, backend="cpu",
            secure_exchange=True,
        )

    max_t = max(tenant_counts)
    keysets = []
    for i in range(max_t):
        r = np.random.default_rng(50 + i)
        sites = r.integers(0, 1 << L, size=8)
        pts = sites[r.integers(0, 8, size=n)]
        pts_bits = (
            ((pts[:, None, None] >> np.arange(L - 1, -1, -1)) & 1) > 0
        )
        keysets.append(ibdcf.gen_l_inf_ball(pts_bits, 1, r, engine="np"))

    async def _pair(p):
        cfg = mkcfg(p)
        s0 = rpc.CollectorServer(0, cfg)
        s1 = rpc.CollectorServer(1, cfg)
        t1 = asyncio.create_task(
            s1.start("127.0.0.1", p + 10, "127.0.0.1", p + 11)
        )
        await asyncio.sleep(0.05)
        t0 = asyncio.create_task(
            s0.start("127.0.0.1", p, "127.0.0.1", p + 11)
        )
        await asyncio.gather(t0, t1)
        return cfg, s0, s1

    async def leg(p, idxs):
        """The collections named by keyset indices ``idxs``, concurrent
        on one fresh pair; returns (results by collection, crawl wall
        seconds, scheduler stats)."""
        cfg, s0, s1 = await _pair(p)
        drv = MultiCollectionDriver(
            cfg, "127.0.0.1", p, "127.0.0.1", p + 10
        )
        leads = {}
        for i in idxs:
            key = f"t{i}" if len(idxs) > 1 else "default"
            lead = await drv.open(key)
            await lead.upload_keys(*keysets[i])
            await lead.warmup()  # WarmLadder dedups across tenants
            leads[key] = (lead, i)
        t0 = time.perf_counter()
        out = await asyncio.gather(
            *(lead.run(n) for lead, _ in leads.values())
        )
        wall = time.perf_counter() - t0
        st = await next(iter(leads.values()))[0].c0.call("status")
        await drv.close()
        for s in (s0, s1):
            await s.aclose()
        results = {
            key: res for (key, (_, i)), res in zip(leads.items(), out)
        }
        return results, wall, st["sessions"]["scheduler"]

    # solo references: each keyset alone on a fresh pair
    solo = {}
    solo_wall = None
    for i in range(max_t):
        res, wall, _sched = asyncio.run(leg(port + 100 + 20 * i, [i]))
        solo[i] = res["default"]
        if i == 0:
            solo_wall = wall
    solo_rate = n / solo_wall

    out = {
        "n_clients_per_tenant": n,
        "data_len": L,
        "solo_clients_per_sec": round(solo_rate, 1),
        "tenants": {},
    }
    for idx, k in enumerate(tenant_counts):
        if k == 1:
            out["tenants"]["1"] = {
                "aggregate_clients_per_sec": round(solo_rate, 1),
                "speedup_vs_solo": 1.0,
                "stall_fill_ratio": 0.0,
            }
            continue
        results, wall, sched = asyncio.run(
            leg(port + 300 + 40 * idx, list(range(k)))
        )
        for i in range(k):
            got = results[f"t{i}"]
            want = solo[i]
            if not (
                np.array_equal(got.counts, want.counts)
                and np.array_equal(got.paths, want.paths)
            ):
                raise AssertionError(
                    f"tenant t{i} of the {k}-collection leg diverged "
                    "from its solo run"
                )
        agg = k * n / wall
        out["tenants"][str(k)] = {
            "aggregate_clients_per_sec": round(agg, 1),
            "speedup_vs_solo": round(agg / solo_rate, 3),
            "stall_fill_ratio": sched["fill_ratio"],
            "stall_fills": sched["stall_fills"],
            "device_turns": sched["device_turns"],
        }
    top = str(max(tenant_counts))
    out["aggregate_clients_per_sec"] = (
        out["tenants"][top]["aggregate_clients_per_sec"]
    )
    out["aggregate_speedup_vs_solo"] = (
        out["tenants"][top]["speedup_vs_solo"]
    )
    out["stall_fill_ratio"] = out["tenants"][top]["stall_fill_ratio"]
    out["bit_identical_vs_solo"] = True
    return out


# sections of the run that already finished, keyed by metric name — what
# the SIGTERM handler dumps so a timed-out bench still reports them
_PARTIAL: dict = {}

# artifact path (--out).  The PARENT owns the file: _child_init clears
# this in bench children so a TERMed child's last-gasp dump can never
# clobber the parent's per-leg artifact (child telemetry travels on the
# stdout contract instead, folded in by _subprocess_metric).
_OUT: str | None = "bench_full.json"


def _atomic_json(path: str, doc: dict) -> None:
    """tmp + rename so a kill mid-write leaves the PREVIOUS artifact
    intact, never a truncated JSON file — the whole point of writing
    per leg is that the file on disk is valid at every instant."""
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        pass


def _write_leg_artifact() -> None:
    """Crash-proof bench: every completed leg lands in the on-disk
    artifact AS IT FINISHES, in the partial form (``"partial": true``
    until main() closes the manifest with the final document).  A bench
    killed at any point leaves a valid artifact carrying every leg that
    completed, and ``--resume`` picks up from exactly there."""
    if _OUT is None:
        return
    _atomic_json(_OUT, {
        "partial": True,
        "reason": "in-progress",
        "results": dict(_PARTIAL),
    })


def _load_resume(path: str) -> dict:
    """Previously-completed legs from an existing artifact: the partial
    form's ``results`` or — resuming over a CLOSED manifest — the final
    form's ``extra`` (mapping its ``secure_crawl`` key back to the
    ``secure`` leg name the partial path uses)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(doc, dict):
        return {}
    if doc.get("partial"):
        res = dict(doc.get("results") or {})
    else:
        res = dict(doc.get("extra") or {})
        res.pop("reference_key_bytes", None)
        if "secure_crawl" in res:
            res["secure"] = res.pop("secure_crawl")
        if "keygen_sweep" in res and "value" in doc:
            res["keygen_headline"] = doc["value"]
    sweep = res.get("keygen_sweep")
    if isinstance(sweep, dict):
        try:  # JSON round-trips the data_len keys as strings
            res["keygen_sweep"] = {int(k): v for k, v in sweep.items()}
        except (TypeError, ValueError):
            pass
    return res


def _dump_partial(reason: str = "sigterm") -> dict:
    """Last-gasp artifact: finished sections plus the telemetry run
    report — the FULL document goes to the ``--out`` artifact (and the
    telemetry to ``$FHH_RUN_REPORT`` when set); the LAST stdout line (the
    bench output contract) carries the COMPACT form, because the harness
    keeps only a short stdout tail and an oversized line parses as
    nothing at all (BENCH_r04)."""
    from fuzzyheavyhitters_tpu import obs

    rep = {
        "partial": True,
        "reason": reason,
        "results": dict(_PARTIAL),
        "telemetry": obs.run_report(),
    }
    if _OUT is not None:
        _atomic_json(_OUT, rep)
    compact = {
        "partial": True,
        "reason": reason,
        "results": _compact_extra(
            {
                k: v
                for k, v in _PARTIAL.items()
                if k not in ("keygen_sweep", "keygen_headline")
            }
        ),
        "sections_done": sorted(_PARTIAL),
    }
    print(json.dumps(compact), flush=True)
    try:
        obs.maybe_write_run_report()
    except Exception:
        pass
    return rep


def _install_sigterm_partial() -> None:
    """SIGTERM -> partial results + telemetry report on stdout, exit 124.
    Installed by main() AND prepended to every child bench process: the
    driver's ``timeout`` command TERMs the run, and before this an rc=124
    bench left nothing but an XLA warning (BENCH_r05) — now it leaves the
    per-level phase seconds and byte counts accumulated up to the kill.
    Also starts the heartbeat: a wedged bench streams the active phase +
    level to stderr every 60 s, so even a SIGKILL leaves a trail naming
    where it died.

    The handler only raises SystemExit; the dump runs from an atexit hook
    once the stack has unwound.  Dumping inside the handler would grab the
    non-reentrant registry/log locks from a signal frame — if the TERM
    lands while the interrupted code holds one (every obs call does,
    briefly), the dump deadlocks until the parent's grace expires and the
    SIGKILL destroys the artifact this exists to save."""
    import atexit
    import signal
    import sys

    from fuzzyheavyhitters_tpu import obs

    obs.start_heartbeat(60.0)
    terminated = []

    def handler(_sig, _frame):
        terminated.append("sigterm")
        raise SystemExit(124)

    def on_exit():
        if terminated:  # normal exits keep the last-stdout-line contract
            _dump_partial(terminated[0])
        else:
            # the $FHH_RUN_REPORT artifact is promised for EVERY run, not
            # just killed ones — write it without touching stdout
            try:
                obs.maybe_write_run_report()
            except Exception:
                pass

    # Ctrl-C must leave the artifact too: SIGINT has no handler here (the
    # default KeyboardInterrupt keeps child teardown working), but one
    # reaching the top level runs excepthook before atexit — mark it so
    # on_exit dumps the finished sections + telemetry it would otherwise
    # silently discard
    prev_hook = sys.excepthook

    def hook(tp, val, tb):
        if issubclass(tp, KeyboardInterrupt):
            terminated.append("interrupt")
        prev_hook(tp, val, tb)

    sys.excepthook = hook
    atexit.register(on_exit)
    signal.signal(signal.SIGTERM, handler)


def _child_init() -> None:
    """Per-child preamble (prepended by _subprocess_metric): the SIGTERM
    partial contract, plus the live /metrics exporter when
    ``FHH_METRICS_PORT`` is set — the PARENT never binds (it only
    orchestrates; the registries worth scraping live in the children,
    which run serially so the base port never conflicts).  The child's
    artifact path is cleared: its partial dump rides the stdout contract
    only, never the parent's per-leg artifact file."""
    global _OUT

    _OUT = None
    _install_sigterm_partial()
    from fuzzyheavyhitters_tpu.obs import exporter

    exporter.maybe_start("bench")


def _subprocess_metric(code: str, timeout_s: int):
    """Run one benchmark in a child process with a hard timeout so a
    stalled accelerator (or a hung socket loop) can never take down
    the whole bench run — the keygen headline must always print.  On
    timeout the child gets SIGTERM first (its handler prints partial
    results + the telemetry report as its last stdout line) and SIGKILL
    only if it ignores that for 20 s."""
    import subprocess
    import sys

    code = "import bench; bench._child_init();" + code
    # $FHH_RUN_REPORT belongs to the PARENT: a TERMed child would write
    # the file too, and the parent's own exit dump then clobbers it.
    # Child telemetry travels on the stdout contract (last JSON line)
    # instead, which the parent folds into its partial dump.
    env = {k: v for k, v in os.environ.items() if k != "FHH_RUN_REPORT"}
    try:
        p = subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=__file__.rsplit("/", 1)[0],
            env=env,
        )
        timed_out = False
        try:
            out, err = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            timed_out = True
            p.terminate()  # SIGTERM: the child dumps partial + telemetry
            try:
                out, err = p.communicate(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                out, err = p.communicate()
        except BaseException:
            # The parent is being torn down (driver SIGTERM -> SystemExit,
            # Ctrl-C) while blocked in communicate(): pass TERM down so the
            # grandchild stops crawling the accelerator and dumps its own
            # partial + telemetry — folded into _PARTIAL so the parent's
            # last-gasp dump (_dump_partial) carries the wedged section's
            # phase/level accounting out with it.  Grace is SHORT: the
            # harness `timeout -k 10` SIGKILLs the parent 10 s after its
            # TERM, and a 20 s wait here meant the parent died before
            # dumping anything (BENCH_r05: rc=124 with no JSON at all).
            p.terminate()
            try:
                out, _ = p.communicate(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            lines = (out or "").strip().splitlines()
            if lines:
                try:
                    _PARTIAL["interrupted"] = json.loads(lines[-1])
                except ValueError:
                    _PARTIAL["interrupted"] = {"stdout_tail": lines[-1][:500]}
            raise
        lines = (out or "").strip().splitlines()
        if not lines:  # child died before printing — surface its stderr
            tail = (err or "").strip().splitlines()[-3:]
            return {"error": f"child rc={p.returncode}: " + " | ".join(tail)}
        res = json.loads(lines[-1])
        if timed_out and isinstance(res, dict):
            res.setdefault("error", f"timeout after {timeout_s}s")
        return res
    except Exception as e:  # spawn failure, parse failure
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def bench_keygen_smoke(rng, L=64, n=2048):
    """CPU-safe keygen timing for smoke mode (scripts/bench_smoke.sh):
    the host NumPy engine over a tiny batch — exercises the keygen
    section's shape of the contract (headline number + sweep row), not
    the chip throughput."""
    from fuzzyheavyhitters_tpu.ops import ibdcf

    alpha = rng.integers(0, 2, size=(n, 1, 2, L)).astype(bool)
    seeds = rng.integers(0, 2**32, size=(n, 1, 2, 2, 4), dtype=np.uint32)
    side = np.broadcast_to(np.array([True, False]), (n, 1, 2))
    ibdcf.gen_pair_np(seeds[:64], alpha[:64], side[:64])  # warm
    t0 = time.perf_counter()
    k0, _ = ibdcf.gen_pair_np(seeds, alpha, side)
    dt = time.perf_counter() - t0
    kps = n / dt
    return kps, {
        L: {
            "keys_per_sec": round(kps, 1),
            "us_per_key": round(1e6 / kps, 3),
            "key_bytes": _key_wire_bytes(k0),
            "n": n,
            "vs_baseline": None,
            "smoke": True,
        }
    }


# headline scalars each section contributes to the COMPACT final line
# (the harness captures only a short stdout tail, so the final JSON line
# must stay small — BENCH_r04 printed a 3.5 KB line and parsed as null)
_COMPACT_KEYS = {
    "crawl": ("aggregate_clients_per_sec", "ms_per_level_device"),
    "crawl_hbm_max": ("clients_per_sec_steady", "crawl_seconds_e2e"),
    "secure_crawl": (
        "secure_clients_per_sec", "ms_per_level_e2e", "secure_kernel",
        "whole_level_speedup_vs_pipelined",
        "sequential_clients_per_sec", "pipeline_speedup", "slo",
    ),
    # _PARTIAL's key for the same section (the partial-dump path)
    "secure": (
        "secure_clients_per_sec", "ms_per_level_e2e", "secure_kernel",
        "whole_level_speedup_vs_pipelined",
        "sequential_clients_per_sec", "pipeline_speedup", "slo",
    ),
    "secure_device": (
        "secure_device_clients_per_sec", "secure_device_ms_per_level_fe62",
    ),
    "hbm": ("projected_max_clients_one_chip_16gb",),
    "covid": ("covid_clients_per_sec",),
    "hash_margin": ("garble_ms_rounds_8",),
    "upload": ("upload_keys_per_sec",),
    "ingest": (
        "ingest_keys_per_sec", "concurrent_keys_per_sec", "windows",
        "shed", "rejected", "bit_identical_vs_batch", "slo",
    ),
    "multichip": (
        "secure_clients_per_sec", "data_shards", "ici_reduce_seconds",
        "bit_identical", "kernel_shards", "kernel_clients_per_sec",
        "kernel_gather_seconds", "whole_level_speedup_vs_gathered",
    ),
    "multitenant": (
        "aggregate_clients_per_sec", "aggregate_speedup_vs_solo",
        "solo_clients_per_sec", "stall_fill_ratio",
        "bit_identical_vs_solo",
    ),
    "sketch": (
        "malicious_overhead_vs_semi_honest", "sketch_clients_per_sec",
        "semi_honest_clients_per_sec", "bit_identical", "sketch_shards",
        "verify_seconds",
    ),
    "radix": (
        "level_rate_x_k", "speedup_vs_k1", "best_k", "bit_identical",
    ),
}


def _compact_extra(full_extra: dict) -> dict:
    """Headline scalars only — every section keyed by its full name with
    its acceptance-relevant numbers, plus error/skip markers, so the
    parsed line answers 'how fast / what failed' without the detail the
    full artifact (bench_full.json / first stdout line) carries."""
    out = {}
    for name, res in full_extra.items():
        if name in ("keygen_sweep", "reference_key_bytes"):
            continue
        if not isinstance(res, dict):
            out[name] = res
            continue
        if "skipped" in res or "error" in res:
            out[name] = {
                k: res[k] for k in ("skipped", "error") if k in res
            }
            continue
        keep = _COMPACT_KEYS.get(name, ())
        out[name] = {k: res[k] for k in keep if k in res}
    return out


def main(argv=None):
    global _OUT
    import argparse

    from fuzzyheavyhitters_tpu import obs

    ap = argparse.ArgumentParser(
        description="fuzzy-heavy-hitters benchmark suite"
    )
    ap.add_argument(
        "--out", default="bench_full.json",
        help="artifact path (written atomically after every leg)",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="skip legs already present in --out (skipped/errored legs "
             "rerun); a closed manifest resumes too",
    )
    ap.add_argument(
        "--sections", default=None,
        help="comma list of leg names to run; the rest report "
             '{"skipped": "sections"}',
    )
    ap.add_argument(
        "--force", action="store_true",
        help="overwrite a non-empty --out artifact (without this, an "
             "existing results file is refused unless --resume extends "
             "it)",
    )
    args = ap.parse_args(argv)
    _OUT = args.out
    if (
        not args.force
        and not args.resume
        and os.path.exists(_OUT)
        and os.path.getsize(_OUT) > 0
    ):
        ap.error(
            f"{_OUT} already holds results — pass --resume to extend "
            "it or --force to overwrite"
        )
    only = (
        {s.strip() for s in args.sections.split(",") if s.strip()}
        if args.sections
        else None
    )

    # one persistent compile cache shared by every child section (placed
    # by utils/compile_cache.py — $JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache — when bench is imported, here and in each
    # child): the per-bucket crawl programs compile once per HLO, not once
    # per subprocess
    _install_sigterm_partial()
    if args.resume:
        _PARTIAL.update(_load_resume(_OUT))
        if _PARTIAL:
            obs.emit(
                "bench.resume", path=_OUT,
                legs=sorted(
                    k for k in _PARTIAL
                    if k not in ("keygen_sweep", "keygen_headline")
                ),
            )
    # legs that returned {"error": ...} (a dead/timed-out child, a failed
    # write): the artifact and the final line still print, the exit code
    # says so.  Skips (budget / sections / smoke) are not errors.
    errored = []
    if (
        args.resume
        and "keygen_sweep" in _PARTIAL
        and "keygen_headline" in _PARTIAL
    ):
        obs.emit("bench.leg", name="keygen", status="resume-skip")
        headline = float(_PARTIAL["keygen_headline"])
        sweep = _PARTIAL["keygen_sweep"]
    else:
        # a child like every other leg: the parent never initialises a
        # backend (see the module docstring)
        obs.emit("bench.leg", name="keygen", status="run")
        res = _subprocess_metric(
            "import json, bench;print(json.dumps(bench.bench_keygen_leg()))",
            timeout_s=int(max(60, min(900, _budget_left()))),
        )
        if "error" in res:
            errored.append("keygen")
            headline, sweep = 0.0, {"error": res["error"]}
        else:
            headline, sweep = float(res["headline"]), res["sweep"]
            _PARTIAL["keygen_sweep"] = sweep
            _PARTIAL["keygen_headline"] = round(headline, 1)
        _write_leg_artifact()

    def section(name, code, timeout_s, smoke_code=None):
        """One subprocess section under the wall-clock budget: a section
        that cannot fit in the time left (reserve included) is skipped
        with a marker instead of risking the whole artifact.  Completed
        legs land in the artifact immediately (_write_leg_artifact); on
        --resume a leg already present (and not a skip/error marker)
        returns its recorded result without rerunning."""
        prev = _PARTIAL.get(name)
        if (
            args.resume
            and prev is not None
            and not (
                isinstance(prev, dict)
                and ("skipped" in prev or "error" in prev)
            )
        ):
            obs.emit("bench.leg", name=name, status="resume-skip")
            return prev
        if only is not None and name not in only:
            res = {"skipped": "sections"}
        elif BENCH_SMOKE and smoke_code is None:
            res = {"skipped": "smoke"}
        else:
            rem = _budget_left() - _BUDGET_RESERVE_S
            if rem < 60:
                res = {"skipped": "budget"}
            else:
                obs.emit("bench.leg", name=name, status="run")
                res = _subprocess_metric(
                    smoke_code if BENCH_SMOKE else code,
                    timeout_s=int(min(timeout_s, rem)),
                )
        _PARTIAL[name] = res
        _write_leg_artifact()
        if isinstance(res, dict) and "error" in res:
            errored.append(name)
        return res

    # budget-trim order: the acceptance-critical secure sections run
    # right after the keygen headline; the long-tail crawl_hbm_max runs
    # LAST so a tight budget trims it first, not the headline metrics
    secure = section(
        "secure",
        "import json, bench;print(json.dumps(bench.bench_secure()))",
        # headroom for the FIRST round's warmup compiles (the per-bucket
        # ladder × both fields); later rounds hit the persistent compile cache
        timeout_s=720,
        smoke_code=(
            "import json, bench;"
            "print(json.dumps(bench.bench_secure(n=64, L=6, shard_nodes=1,"
            " pipeline_depth=3)))"
        ),
    )
    radix = section(
        "radix",
        "import json, bench;print(json.dumps(bench.bench_radix()))",
        # three warmed secure pairs (k = 1, 2, 3), each with its own
        # fused-shape warmup ladder; later runs hit the persistent compile cache
        timeout_s=900,
        smoke_code=(
            "import json, bench;"
            "print(json.dumps(bench.bench_radix(n=64, L=6)))"
        ),
    )
    multichip = section(
        "multichip",
        "import json, bench;print(json.dumps(bench.bench_multichip()))",
        # warmed legs: 1/2/4/8 data shards plus the kernel-sharded sweep
        # at the top count, each its own server pair with its own
        # sharded program ladder
        timeout_s=900,
        # f_max=32 trims one warmup-ladder rung per leg per field
        # (the zipf smoke frontier peaks at 28 survivors) — the smoke
        # budget must leave room for the ingest section after this;
        # n=512 puts every bucket-16 rung at 16384 tests = 2 planar
        # blocks, so the kernel-sharded legs engage (kernel_shards=2)
        # without depending on the borderline bucket-32 survivors
        smoke_code=(
            "import json, bench;"
            "print(json.dumps(bench.bench_multichip(n=512, L=5,"
            " shards=(1, 2, 4), f_max=32, kernel_shards=(1, 2))))"
        ),
    )
    sketch = section(
        "sketch",
        "import json, bench;print(json.dumps(bench.bench_sketch()))",
        # semi-honest reference + the sketch_shards sweep, each leg its
        # own warmed server pair (fused verify ladder via warmup)
        timeout_s=900,
        # smoke: trusted exchange keeps the compile load inside the
        # budget; the sketch lane (fused verify, sharded legs, both
        # gates) is identical either way
        smoke_code=(
            "import json, bench;"
            "print(json.dumps(bench.bench_sketch(n=64, L=6,"
            " shards=(1, 2), secure=False)))"
        ),
    )
    secure_device = section(
        "secure_device",
        "import json, bench;print(json.dumps(bench.bench_secure_device()))",
        # headroom for the contention-retry path (see bench_secure_device)
        timeout_s=1500,
    )
    crawl = section(
        "crawl",
        "import json, numpy as np, bench;"
        "from fuzzyheavyhitters_tpu.ops import ibdcf;"
        "from fuzzyheavyhitters_tpu.protocol import driver;"
        "print(json.dumps(bench.bench_crawl(ibdcf, driver,"
        " np.random.default_rng(0))))",
        timeout_s=540,
    )
    hbm = section(
        "hbm",
        "import json, bench;print(json.dumps(bench.bench_hbm()))",
        timeout_s=540,
    )
    covid = section(
        "covid",
        "import json, bench;print(json.dumps(bench.bench_covid()))",
        timeout_s=540,
    )
    hash_margin = section(
        "hash_margin",
        "import json, bench;print(json.dumps(bench.bench_hash_margin()))",
        timeout_s=540,
    )
    upload = section(
        "upload",
        "import json, bench;print(json.dumps(bench.bench_upload()))",
        timeout_s=540,
    )
    ingest = section(
        "ingest",
        "import json, bench;print(json.dumps(bench.bench_ingest()))",
        timeout_s=540,
        # smoke: tiny window pair, still concurrent + bit-identity-gated
        smoke_code=(
            "import json, bench;"
            "print(json.dumps(bench.bench_ingest(n=512, L=6, chunk=32,"
            " threshold=0.2)))"
        ),
    )
    multitenant = section(
        "multitenant",
        "import json, bench;print(json.dumps(bench.bench_multitenant()))",
        # 4 solo legs + the 2- and 4-tenant legs, each a fresh secure
        # server pair; warmup rides the shared WarmLadder + compile cache
        timeout_s=900,
        smoke_code=(
            "import json, bench;"
            "print(json.dumps(bench.bench_multitenant(n=64, L=6,"
            " tenant_counts=(1, 2), threshold=0.2)))"
        ),
    )
    crawl_hbm_max = section(
        "crawl_hbm_max",
        "import json, numpy as np, bench;"
        "print(json.dumps(bench.bench_crawl_hbm_max(np.random.default_rng(17))))",
        # a REAL 512-level run plus the one-time 8 GB device->host key
        # fetch: the long-tail leg
        timeout_s=2700,
    )
    # smoke mode must not clobber the tracked chip reference rows with
    # its tiny np-engine sweep (the CSV is the cross-round keygen
    # continuity artifact)
    if not BENCH_SMOKE and "keygen" not in errored:
        try:
            write_keygen_csv(sweep)
        except OSError as e:
            obs.emit("bench.csv_failed", severity="error",
                     error=f"{type(e).__name__}: {e}")
            errored.append("keygen_csv")

    extra = {
        "keygen_sweep": sweep,
        "reference_key_bytes": BASELINE_KEY_BYTES,
        "crawl": crawl,
        "crawl_hbm_max": crawl_hbm_max,
        "secure_crawl": secure,
        "radix": radix,
        "multichip": multichip,
        "sketch": sketch,
        "secure_device": secure_device,
        "hbm": hbm,
        "covid": covid,
        "hash_margin": hash_margin,
        "upload": upload,
        "ingest": ingest,
        "multitenant": multitenant,
    }
    head = {
        "metric": "ibdcf_keygen_keys_per_sec_at_data_len_512",
        "value": round(headline, 1),
        "unit": "keys/s/chip",
        "vs_baseline": round(headline / BASELINE_KEYS_PER_SEC, 2),
    }
    if BENCH_SMOKE:
        head["metric"] = "ibdcf_keygen_keys_per_sec_smoke_np"
        head["vs_baseline"] = None
    budget_info = {
        "budget_s": BENCH_BUDGET_S,
        "elapsed_s": round(time.monotonic() - _BENCH_T0, 1),
        "smoke": BENCH_SMOKE,
    }
    full = dict(head, extra=extra, budget=budget_info)
    # full artifact: closing the manifest — the atomic rewrite replaces
    # the per-leg partial form (no "partial" key ever again) — plus the
    # first stdout line (for humans and transcripts); NOT the last line,
    # which must stay parseable
    _atomic_json(_OUT, full)
    print(json.dumps(full), flush=True)
    # the LAST stdout line is the machine contract: the harness keeps a
    # short tail, so it gets the compact form (headline + per-section
    # acceptance scalars), guaranteed to stay small
    print(
        json.dumps(dict(head, extra=_compact_extra(extra), budget=budget_info)),
        flush=True,
    )
    if errored:
        obs.emit("bench.errored", severity="error", legs=errored)
    return 1 if errored else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
