"""End-to-end protocol tests: in-process leader + two colocated servers vs a
brute-force heavy-hitters oracle (the integration-test shape of the
reference's collect_test.rs: known multiset in, exact counts out —
SURVEY.md §4)."""

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import ibdcf
from fuzzyheavyhitters_tpu.protocol import collect, driver
from fuzzyheavyhitters_tpu.utils import bits as bitutils


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """Colocated-driver e2e pinned to the first CPU device."""
    yield


def brute_force_hitters(pts, ball, L, thresh):
    """All leaves x where #{clients whose saturated L∞ ball contains x} >=
    thresh, with exact counts.  pts: int[N, d]."""
    pts = np.asarray(pts)
    n, d = pts.shape
    lo = np.clip(pts - ball, 0, (1 << L) - 1)
    hi = np.clip(pts + ball, 0, (1 << L) - 1)
    out = {}
    grid = np.stack(
        np.meshgrid(*[np.arange(1 << L)] * d, indexing="ij"), axis=-1
    ).reshape(-1, d)
    for x in grid:
        c = int(np.sum(np.all((x >= lo) & (x <= hi), axis=1)))
        if c >= thresh:
            out[tuple(int(v) for v in x)] = c
    return out


def run_protocol(pts, ball, L, threshold, f_max=128):
    pts = np.asarray(pts)
    n, d = pts.shape
    rng = np.random.default_rng(99)
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, ball, rng)
    s0, s1 = driver.make_servers(k0, k1)
    lead = driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=f_max)
    res = lead.run(nreqs=n, threshold=threshold)
    got = {}
    for i in range(res.paths.shape[0]):
        key = tuple(int(v) for v in res.decode_ints()[i])
        got[key] = int(res.counts[i])
    return got


@pytest.mark.parametrize("d,L,ball", [(1, 6, 3), (2, 5, 2)])
def test_heavy_hitters_match_brute_force(rng, d, L, ball):
    n = 40
    # clustered points so some leaves clear the threshold
    centers = rng.integers(0, 1 << L, size=(4, d))
    pts = centers[rng.integers(0, 4, size=n)] + rng.integers(-1, 2, size=(n, d))
    pts = np.clip(pts, 0, (1 << L) - 1)
    threshold = 0.1  # thresh = max(1, 4)
    got = run_protocol(pts, ball, L, threshold, f_max=512 if d == 2 else 128)
    want = brute_force_hitters(pts, ball, L, max(1, int(threshold * n)))
    assert got == want


def test_no_survivors_early_exit(rng):
    pts = np.array([[3], [10], [40]])
    got = run_protocol(pts, 1, 6, threshold=0.99)  # thresh=2, balls disjoint
    assert got == {}


def test_single_client_threshold_one(rng):
    """threshold*nreqs < 1 floors to 1 (ref: leader.rs:193)."""
    pts = np.array([[17]])
    got = run_protocol(pts, 2, 6, threshold=0.0001)
    want = brute_force_hitters(pts, 2, 6, 1)
    assert got == want


def test_liveness_flag_gates_counts(rng):
    """Disabling a client's liveness flag removes it from every count
    (ref: collect.rs:495 — the hook the sketch verification uses)."""
    pts = np.array([[8], [8], [8], [50]])
    L, ball = 6, 1
    pts_bits = np.array([[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts])
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, ball, np.random.default_rng(5))
    s0, s1 = driver.make_servers(k0, k1)
    s0.alive_keys[0] = False
    s1.alive_keys[0] = False
    lead = driver.Leader(s0, s1, n_dims=1, data_len=L, f_max=128)
    res = lead.run(nreqs=4, threshold=0.5)  # thresh=2
    got = {tuple(r): c for r, c in zip(res.decode_ints(), res.counts)}
    # only two live clients at 8 remain above threshold
    assert set(got) == {(7,), (8,), (9,)}
    assert all(c == 2 for c in got.values())


def test_f_max_overflow_raises(rng):
    pts = np.tile(np.arange(0, 64, 2)[:, None], (1, 1))  # 32 spread clients
    with pytest.raises(ValueError, match="f_max"):
        run_protocol(pts, 3, 6, threshold=0.001, f_max=4)


def test_pattern_masks_layout():
    m = collect.pattern_masks(2)
    assert m.shape == (4,)
    # pattern 0: dirs (0,0) -> bits at (j*4 + s*2 + 0)
    assert m[0] == sum(1 << (j * 4 + s * 2) for j in range(2) for s in range(2))
    # pattern 3: dirs (1,1)
    assert m[3] == sum(1 << (j * 4 + s * 2 + 1) for j in range(2) for s in range(2))


def test_bucket_for_and_compact_survivors():
    """Bucketed-frontier helpers: power-of-2 sizing with the f_max cap and
    the min_bucket pin, and compact_survivors padding to the bucket."""
    assert [collect.bucket_for(n, 64) for n in (0, 1, 2, 3, 4, 5, 33, 64)] == [
        1, 1, 2, 4, 4, 8, 64, 64,
    ]
    assert collect.bucket_for(3, 64, min_bucket=16) == 16
    assert collect.bucket_for(60, 64, min_bucket=16) == 64
    with pytest.raises(ValueError, match="f_max"):
        collect.bucket_for(65, 64)
    keep = np.zeros((4, 2), bool)
    keep[0, 1] = keep[2, 0] = keep[3, 1] = True
    parent, pattern, n_alive = collect.compact_survivors(keep, 64)
    assert n_alive == 3 and parent.shape == (4,)  # padded to bucket 4
    assert parent[:3].tolist() == [0, 2, 3]
    assert pattern[:3].tolist() == [1, 0, 1]
    assert parent[3] == 0 and pattern[3] == 0  # zero padding


@pytest.mark.parametrize("on_chip", [False, True])
def test_streamed_crawl_matches_resident(rng, on_chip):
    """The HBM-overflow streaming mode (host-resident keys, per-level cw
    upload, cache-free donated advance) produces the identical crawl as
    the resident-key driver — on the CPU/XLA engine and, where a chip is
    present, on the planar Pallas engine (which exercises the in-layout
    gather -> kernel-expand -> select advance)."""
    import jax

    if on_chip and jax.devices()[0].platform != "tpu":
        pytest.skip("needs a TPU backend")
    # the module fixture pins CPU; the chip variant must override it back
    ctx = jax.default_device(
        jax.devices()[0] if on_chip else jax.devices("cpu")[0]
    )
    with ctx:
        L, n, d = 12, 300, 1
        centers = rng.integers(0, 1 << L, size=(5, d))
        pts = np.clip(
            centers[rng.integers(0, 5, size=n)]
            + rng.integers(-2, 3, size=(n, d)),
            0, (1 << L) - 1,
        )
        pts_bits = np.array(
            [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
        )
        k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, 2, rng, engine="np")
        host = lambda k: type(k)(*[np.asarray(x) for x in k])
        s0, s1 = driver.make_servers(k0, k1)
        res = driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=128).run(
            nreqs=n, threshold=0.05
        )
        t0, t1 = driver.make_servers(host(k0), host(k1))
        res_s = driver.Leader(
            t0, t1, n_dims=d, data_len=L, f_max=128, stream=True
        ).run(nreqs=n, threshold=0.05)
        np.testing.assert_array_equal(res.paths, res_s.paths)
        np.testing.assert_array_equal(
            np.asarray(res.counts), np.asarray(res_s.counts)
        )
        assert res.paths.shape[0] >= 1


def test_checkpoint_resume_matches_uninterrupted(rng, tmp_path):
    """A crawl interrupted after a mid-crawl checkpoint and resumed by a
    FRESH leader (same keys, state restored from disk) produces the exact
    uninterrupted heavy hitters — including the leader-side path
    bookkeeping and liveness flags the checkpoint must carry."""
    L, d, n, ball, threshold = 8, 1, 40, 2, 0.1
    centers = rng.integers(0, 1 << L, size=(4, d))
    pts = np.clip(
        centers[rng.integers(0, 4, size=n)] + rng.integers(-1, 2, size=(n, d)),
        0, (1 << L) - 1,
    )
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    krng = np.random.default_rng(99)
    k0, k1 = ibdcf.gen_l_inf_ball(pts_bits, ball, krng, engine="np")

    def as_dict(res):
        return {
            tuple(int(v) for v in r): int(c)
            for r, c in zip(res.decode_ints(), res.counts)
        }

    s0, s1 = driver.make_servers(k0, k1)
    want = as_dict(
        driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=64).run(
            nreqs=n, threshold=threshold
        )
    )
    assert want  # non-degenerate scenario

    ck = str(tmp_path / "crawl.npz")
    # first leader: run HALF the levels with periodic checkpoints, then
    # "crash" (simply stop driving it)
    s0a, s1a = driver.make_servers(k0, k1)
    lead_a = driver.Leader(s0a, s1a, n_dims=d, data_len=L, f_max=64)
    lead_a.tree_init()
    for level in range(L // 2):
        assert lead_a.run_level(level, nreqs=n, threshold=threshold) > 0
    lead_a.checkpoint(ck, L // 2 - 1)

    # resume-safety guards, checked against the on-disk file BEFORE the
    # successful resume consumes it:
    # (a) different leader shape -> refused
    s0c, s1c = driver.make_servers(k0, k1)
    lead_c = driver.Leader(s0c, s1c, n_dims=d, data_len=L, f_max=128)
    with pytest.raises(ValueError, match="checkpoint shape"):
        lead_c.restore(ck)
    # (b) same shape, DIFFERENT key batches -> refused (resuming crawl A's
    # frontier under crawl B's keys would yield silently wrong counts)
    ok0, ok1 = ibdcf.gen_l_inf_ball(
        pts_bits, ball, np.random.default_rng(7), engine="np"
    )
    s0d, s1d = driver.make_servers(ok0, ok1)
    lead_d = driver.Leader(s0d, s1d, n_dims=d, data_len=L, f_max=64)
    with pytest.raises(ValueError, match="different key batches"):
        lead_d.restore(ck)
    # (b') same RNG seed, DIFFERENT ball radius -> refused.  Root seeds
    # are identical here and the correction words diverge only at the
    # DEEP levels (the radius perturbs the interval endpoints' low bits),
    # so this pins that the fingerprint covers the full level axis.
    bk0, bk1 = ibdcf.gen_l_inf_ball(
        pts_bits, ball + 1, np.random.default_rng(99), engine="np"
    )
    np.testing.assert_array_equal(
        np.asarray(bk0.root_seed), np.asarray(k0.root_seed)
    )  # the scenario is real: only the cw planes differ
    s0g, s1g = driver.make_servers(bk0, bk1)
    lead_g = driver.Leader(s0g, s1g, n_dims=d, data_len=L, f_max=64)
    with pytest.raises(ValueError, match="different key batches"):
        lead_g.restore(ck)

    # fresh leader over the SAME keys resumes from disk; run()-written
    # checkpoints also carry (nreqs, threshold), so a mid-crawl file from
    # run() refuses a resume under a different pruning regime — exercise
    # that via a run()-produced checkpoint after this resume completes
    import os

    s0b, s1b = driver.make_servers(k0, k1)
    lead_b = driver.Leader(s0b, s1b, n_dims=d, data_len=L, f_max=64)
    got = as_dict(
        lead_b.run(nreqs=n, threshold=threshold, checkpoint_path=ck, resume=True)
    )
    assert got == want
    # (c) a COMPLETED crawl removes its checkpoint: the always-resume
    # invocation starts the next crawl fresh instead of resuming this one
    assert not os.path.exists(ck)

    # (d) param guard: a run()-written mid-crawl checkpoint refuses resume
    # under a different threshold
    s0e, s1e = driver.make_servers(k0, k1)
    lead_e = driver.Leader(s0e, s1e, n_dims=d, data_len=L, f_max=64)
    lead_e.tree_init()
    for level in range(L // 2):
        lead_e.run_level(level, nreqs=n, threshold=threshold)
    lead_e.checkpoint(ck, L // 2 - 1, nreqs=n, threshold=threshold)
    s0f, s1f = driver.make_servers(k0, k1)
    lead_f = driver.Leader(s0f, s1f, n_dims=d, data_len=L, f_max=64)
    with pytest.raises(ValueError, match="crawl params"):
        lead_f.run(
            nreqs=n, threshold=0.5, checkpoint_path=ck, resume=True
        )


@pytest.mark.parametrize("client", [2, 79])
def test_key_fingerprint_covers_every_client(client):
    """The fingerprint's client-axis checksum covers EVERY client: two
    key batches with identical roots that diverge at any single client —
    an interior one (2: unsampled by any 64-slot prefix or spread
    sample of 80) or the endpoint (79) — must fingerprint differently."""
    L, d, n = 6, 1, 80
    rng = np.random.default_rng(3)
    pts = rng.integers(0, 1 << L, size=(n, d))
    pts2 = pts.copy()
    pts2[client] = (pts2[client] + 1) % (1 << L)  # ONE client differs

    def keys(p):
        bits = np.array(
            [[bitutils.int_to_bits(L, int(v)) for v in row] for row in p]
        )
        return ibdcf.gen_l_inf_ball(
            bits, 1, np.random.default_rng(11), engine="np"
        )

    def fingerprint(k0, k1):
        s0, s1 = driver.make_servers(k0, k1)
        lead = driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=64)
        return lead._key_fingerprint()

    ka = keys(pts)
    kb = keys(pts2)
    # the scenario is real: same rng seed -> identical roots, so only the
    # cw planes (at the one divergent client) can tell the batches apart
    np.testing.assert_array_equal(
        np.asarray(ka[0].root_seed), np.asarray(kb[0].root_seed)
    )
    fp_a, fp_b = fingerprint(*ka), fingerprint(*kb)
    assert not np.array_equal(fp_a, fp_b)
    # and identical batches still agree (the fingerprint is deterministic)
    assert np.array_equal(fp_a, fingerprint(*keys(pts)))


def test_checkpoint_resume_streaming_mode(rng, tmp_path):
    """Checkpoint/resume under the STREAMING crawl mode (host-resident
    keys, per-level cw upload — the mode the flagship 512-level runs use):
    a streamed crawl interrupted mid-crawl and resumed by a fresh streamed
    leader matches the uninterrupted resident-key result, with the cw
    window caches rebuilt lazily after restore."""
    L, d, n = 8, 1, 60
    centers = rng.integers(0, 1 << L, size=(4, d))
    pts = np.clip(
        centers[rng.integers(0, 4, size=n)] + rng.integers(-1, 2, size=(n, d)),
        0, (1 << L) - 1,
    )
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    k0, k1 = ibdcf.gen_l_inf_ball(
        pts_bits, 2, np.random.default_rng(5), engine="np"
    )
    host = lambda k: type(k)(*[np.asarray(x) for x in k])

    def as_dict(res):
        return {
            tuple(int(v) for v in r): int(c)
            for r, c in zip(res.decode_ints(), res.counts)
        }

    s0, s1 = driver.make_servers(k0, k1)
    want = as_dict(
        driver.Leader(s0, s1, n_dims=d, data_len=L, f_max=64).run(
            nreqs=n, threshold=0.1
        )
    )
    assert want

    ck = str(tmp_path / "stream.npz")
    t0, t1 = driver.make_servers(host(k0), host(k1))
    lead_a = driver.Leader(
        t0, t1, n_dims=d, data_len=L, f_max=64, stream=True, stream_window=4
    )
    lead_a.tree_init()
    for level in range(5):  # crosses a stream-window boundary (4)
        assert lead_a.run_level(level, nreqs=n, threshold=0.1) > 0
    lead_a.checkpoint(ck, 4)

    u0, u1 = driver.make_servers(host(k0), host(k1))
    lead_b = driver.Leader(
        u0, u1, n_dims=d, data_len=L, f_max=64, stream=True, stream_window=4
    )
    got = as_dict(
        lead_b.run(nreqs=n, threshold=0.1, checkpoint_path=ck, resume=True)
    )
    assert got == want


def test_checkpoint_layout_conversion_roundtrip(rng):
    """_convert_layout is the involutive planar<->interleaved transpose
    pair (the engine edges of collect.advance): converting a synthetic
    interleaved state to planar and back is the identity, and the planar
    form has the documented [4, d, 2, F, N] / [d, 2, F, N] shapes."""
    from fuzzyheavyhitters_tpu.ops.ibdcf import EvalState

    F, N, d = 3, 7, 2
    st = EvalState(
        seed=rng.integers(0, 2**32, size=(F, N, d, 2, 4), dtype=np.uint32),
        bit=rng.integers(0, 2, size=(F, N, d, 2)).astype(bool),
        y_bit=rng.integers(0, 2, size=(F, N, d, 2)).astype(bool),
    )
    planar = driver._convert_layout(st, from_planar=False)
    assert planar.seed.shape == (4, d, 2, F, N)
    assert planar.bit.shape == (d, 2, F, N)
    back = driver._convert_layout(planar, from_planar=True)
    for a, b in zip(st, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_covid_crawl_end_to_end(rng, tmp_path):
    """COVID workload driven end to end: the f64-bit domain (data_len=64,
    n_dims=2, ref: sample_covid_data.rs:32-35) through the full driver
    crawl, checked against a direct interval oracle in u64 bit-space.
    Jitterless sampling makes same-county clients bit-identical, so the
    heavy hitters are each hot county's f64 pattern plus its L∞-ball
    neighbourhood in ulp space."""
    from fuzzyheavyhitters_tpu.workloads import covid

    csv_path = tmp_path / "county_centroids.csv"
    csv_path.write_text(
        "fips_code,latitude,longitude\n"
        "01001,32.53,-86.64\n"
        "06037,34.05,-118.24\n"
        "48453,30.26,-97.74\n"
    )
    n, L, ball = 24, 64, 1
    pts = covid.sample_covid_locations(
        str(tmp_path / "absent.csv"), str(csv_path), n,
        fuzz_factor=None, seed=3,
    )
    assert pts.shape == (n, 2, L)
    k0, k1 = ibdcf.gen_l_inf_ball(pts, ball, rng, engine="np")
    s0, s1 = driver.make_servers(k0, k1)
    lead = driver.Leader(
        s0, s1, n_dims=2, data_len=L, f_max=64, min_bucket=64
    )
    threshold = 0.2  # thresh = max(1, 4)
    res = lead.run(nreqs=n, threshold=threshold)
    got = {
        tuple(int(v) for v in res.decode_ints()[i]): int(res.counts[i])
        for i in range(res.paths.shape[0])
    }

    # oracle: u64 interpretation of the f64 bit patterns; ball membership
    # is a saturating per-dim interval test (utils/bits semantics)
    ints = np.zeros((n, 2), np.uint64)
    for i in range(n):
        for d_ in range(2):
            v = 0
            for b in pts[i, d_]:
                v = (v << 1) | int(b)
            ints[i, d_] = v
    lo = np.maximum(ints, ball) - ball  # saturating p - ball
    hi = ints + ball
    hi[hi < ints] = np.uint64(2**64 - 1)  # saturating p + ball
    thresh = max(1, int(threshold * n))
    cand = set()
    for i in range(n):
        for dx in range(-ball, ball + 1):
            for dy in range(-ball, ball + 1):
                x = int(ints[i, 0]) + dx
                y = int(ints[i, 1]) + dy
                if 0 <= x < 2**64 and 0 <= y < 2**64:
                    cand.add((x, y))
    want = {}
    for x, y in cand:
        c = int(np.sum((lo[:, 0] <= x) & (x <= hi[:, 0])
                       & (lo[:, 1] <= y) & (y <= hi[:, 1])))
        if c >= thresh:
            want[(x, y)] = c
    assert got == want
    assert len(got) >= 3  # every hot county survives with its ulp ball
    # decoded leaves round-trip to the sampled coordinates
    lats = {round(covid.bool_vec_to_f64(pts[i, 0]), 2) for i in range(n)}
    got_lats = {
        round(covid.bool_vec_to_f64(bitutils.int_to_bits(64, x)), 2)
        for (x, _) in got
    }
    assert got_lats <= {l for l in lats} | {32.53, 34.05, 30.26}
