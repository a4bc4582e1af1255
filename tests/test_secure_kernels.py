"""Device-resident secure-kernel tests: the GF(2^128) algebra under the
1-of-2^S equality OT, ot_hash tweak-domain separation, engine parity of
the planar packed wire (XLA twins vs Pallas interpret), cross-parity of
the 1-of-2^S path against the GC path for S ∈ {2, 4, 6} on both fields,
mid-level ``idx_offset`` continuity across batches, the whole-level
socket flow (phase split, ot_path telemetry, whole-level vs sharded
bit-identity, a 2-dim oracle run), and the warmed-crawl
zero-fresh-compiles contract."""

import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from fuzzyheavyhitters_tpu.obs import report as obsreport
from fuzzyheavyhitters_tpu.ops import gc, gc_pallas, ibdcf, otext, otext_pallas
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.protocol import driver, rpc, secure
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

BASE_PORT = 30131  # a range of its own: 21531 + offsets ran into test_sketch, test_resilience and test_binaries_e2e under xdist


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """All tests in this module run on the CPU backend (see conftest)."""
    yield


# ---------------------------------------------------------------------------
# GF(2^128) algebra: doubling, comb, offsets
# ---------------------------------------------------------------------------

_POLY = 0x87  # x^128 = x^7 + x^2 + x + 1 (otext.gf128_double's constant)


def _ref_int(block) -> int:
    return int.from_bytes(np.asarray(block, "<u4").tobytes(), "little")


def _ref_double(v: int) -> int:
    v <<= 1
    if v >> 128:
        v = (v ^ _POLY) & ((1 << 128) - 1)
    return v


def test_gf128_double_matches_bigint_reference(rng):
    x = rng.integers(0, 2**32, size=(32, 4), dtype=np.uint32)
    got = np.asarray(otext.gf128_double(x))
    for row, out in zip(x, got):
        assert _ref_int(out) == _ref_double(_ref_int(row))


def test_gf128_double_field_identities(rng):
    """Doubling is GF(2^128)-linear and invertible: 2(x^y) = 2x^2y, the
    map is injective on a sample, and 2^128 applications reduce to the
    known field element x^128 = 0x87 when starting from 1."""
    x = rng.integers(0, 2**32, size=(16, 4), dtype=np.uint32)
    y = rng.integers(0, 2**32, size=(16, 4), dtype=np.uint32)
    dbl = lambda a: np.asarray(otext.gf128_double(a))
    np.testing.assert_array_equal(dbl(x ^ y), dbl(x) ^ dbl(y))
    assert len({bytes(r) for r in dbl(x)}) == len(x)
    one = np.zeros((1, 4), np.uint32)
    one[0, 0] = 1
    acc = one
    for _ in range(128):
        acc = dbl(acc)
    assert _ref_int(acc[0]) == _POLY


def test_gf128_comb_is_the_coefficient_sum(rng):
    """comb(rows) == ⊕_j x^j·rows_j against the bigint reference, for
    every S the ot2s path ships."""
    for S in (2, 4, 6):
        rows = rng.integers(0, 2**32, size=(5, S, 4), dtype=np.uint32)
        got = np.asarray(otext.gf128_comb(rows))
        for b in range(5):
            want = 0
            for j in range(S):
                v = _ref_int(rows[b, j])
                for _ in range(j):
                    v = _ref_double(v)
                want ^= v
            assert _ref_int(got[b]) == want, (S, b)


def test_gf128_offsets_distinct_and_linear(rng):
    """The 2^S offset table is pairwise distinct (the 1-of-2^S privacy
    argument) and GF(2)-linear in the choice: o_c ^ o_c' == o_{c^c'}."""
    s = np.asarray(otext.s_to_block(otext.fresh_s_bits()))
    for S in (2, 4, 6):
        offs = np.asarray(otext.gf128_offsets(s, S))
        assert len({bytes(o) for o in offs}) == 1 << S, S
        c1, c2 = 0b0110 % (1 << S), 0b1011 % (1 << S)
        np.testing.assert_array_equal(offs[c1] ^ offs[c2], offs[c1 ^ c2])


# ---------------------------------------------------------------------------
# ot_hash: tweak-domain and index separation
# ---------------------------------------------------------------------------


def test_ot_hash_domain_separation(rng):
    """Identical rows at identical indices hash independently per
    tweak-domain — the property that lets the per-TEST 1-of-2^S pads
    share an index range with the per-ROW Δ-OT pads."""
    rows = rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32)
    p0 = np.asarray(otext.ot_hash(rows, 4, 0))
    p1 = np.asarray(otext.ot_hash(rows, 4, 0, domain=secure._OT2S_DOMAIN))
    assert not np.array_equal(p0, p1)
    assert (p0 != p1).any(axis=1).all()  # every row separated


def test_ot_hash_index_separation_and_offset(rng):
    """The same row at different positions hashes differently, and
    ``idx_offset`` IS the position: H(row, idx_offset=k) equals row k of
    a batch hash starting at 0 — the invariant mid-level batch
    continuity rests on."""
    row = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    tiled = np.broadcast_to(row, (8, 4)).copy()
    pads = np.asarray(otext.ot_hash(tiled, 4, 0))
    assert len({bytes(p) for p in pads}) == 8
    single = np.asarray(otext.ot_hash(row[None], 4, 7))
    np.testing.assert_array_equal(single[0], pads[7])


# ---------------------------------------------------------------------------
# 1-of-2^S: engine parity + cross-parity against the GC path
# ---------------------------------------------------------------------------


def _delta_rows(qr, y, s):
    """Receiver rows t_j = q_j ^ y_j·s from sender rows (the Δ-OT law)."""
    B, S = y.shape
    flat = np.where(
        y.reshape(B * S, 1), qr.reshape(B * S, 4) ^ s, qr.reshape(B * S, 4)
    )
    return flat.reshape(B, S, 4)


def _ot2s_planar_parity(rng, S, field):
    B = 40
    W = secure.payload_words(field)
    s = np.asarray(otext.s_to_block(otext.fresh_s_bits()))
    qr = rng.integers(0, 2**32, size=(B, S, 4), dtype=np.uint32)
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    y[::3] = ~y[::3]
    m0 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    m1 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    msg_x = np.asarray(secure._ot2s_encrypt_packed_xla(
        jnp.asarray(qr), jnp.asarray(s), jnp.asarray(x), jnp.asarray(m0),
        jnp.asarray(m1), W, 17,
    ))
    msg_p = np.asarray(otext_pallas.ot2s_encrypt(
        qr, s, x, m0, m1, W, 17, domain=secure._OT2S_DOMAIN, interpret=True
    ))
    np.testing.assert_array_equal(msg_x, msg_p)
    tr = _delta_rows(qr, y, s)
    pay_x = np.asarray(secure._ot2s_decrypt_packed_xla(
        jnp.asarray(tr), jnp.asarray(y), jnp.asarray(msg_x), S, W, 17
    ))
    pay_p = np.asarray(otext_pallas.ot2s_decrypt(
        tr, y, msg_p, W, 17, domain=secure._OT2S_DOMAIN, interpret=True
    ))
    np.testing.assert_array_equal(pay_x, pay_p)
    eq = np.all(x == y, axis=1)
    np.testing.assert_array_equal(pay_x, np.where(eq[:, None], m1, m0))


@pytest.mark.parametrize("S", [2, 4])
@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
def test_ot2s_planar_engine_parity(rng, S, field):
    """The planar wire buffer is BYTE-identical between the XLA twin and
    the Pallas kernel (interpret mode), padding included, and opens to
    the right payload."""
    _ot2s_planar_parity(rng, S, field)


@pytest.mark.slow
@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
def test_ot2s_planar_engine_parity_s6(rng, field):
    """S = 6 engine parity (slow-marked: the 64-choice interpret-mode
    kernel compiles in tens of seconds on XLA:CPU)."""
    _ot2s_planar_parity(rng, 6, field)


def test_gc_packed_engine_parity(rng):
    """The packed whole-level garbled message is byte-identical between
    the XLA twin and the Pallas kernel, and its eval twins agree."""
    B, S, W = 24, 4, 4
    s = np.asarray(otext.s_to_block(otext.fresh_s_bits()))
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    Y0 = rng.integers(0, 2**32, size=(B, S, 4), dtype=np.uint32)
    seed = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    m0 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    m1 = rng.integers(0, 2**32, size=(B, W), dtype=np.uint32)
    msg_x, mask_x = gc._garble_equality_payload_packed_xla(
        jnp.asarray(s), jnp.asarray(Y0), jnp.asarray(seed), jnp.asarray(x),
        jnp.asarray(m0), jnp.asarray(m1), W, 3,
    )
    msg_p, mask_p = gc_pallas.garble_equality_payload_packed(
        s, Y0, seed, x, m0, m1, W, 3, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(msg_x), np.asarray(msg_p))
    np.testing.assert_array_equal(np.asarray(mask_x), np.asarray(mask_p))
    assert np.asarray(msg_x).size == gc_pallas.packed_msg_words(B, S, W)
    ev = Y0 ^ np.where(x[..., None], s, np.zeros(4, np.uint32))
    e_x, pay_x = gc._eval_equality_payload_packed_xla(
        msg_x, jnp.asarray(ev), S, W, 3
    )
    e_p, pay_p = gc_pallas.eval_equality_payload_packed(
        np.asarray(msg_p), ev, W, 3, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(e_x), np.asarray(e_p))
    np.testing.assert_array_equal(np.asarray(pay_x), np.asarray(pay_p))
    np.testing.assert_array_equal(np.asarray(pay_x), m1)  # y == x: all equal


@pytest.mark.parametrize(
    # every (S, field) pair; the garbler sign (a ±1 in the payload pair,
    # path-independent) is swept once at the cheapest shape
    "S,field,garbler",
    [
        pytest.param(s, f, 0, id=f"S{s}-{fn}-g0")
        for s in (2, 4, 6) for f, fn in ((FE62, "FE62"), (F255, "F255"))
    ] + [pytest.param(2, FE62, 1, id="S2-FE62-g1")],
)
def test_ot2s_cross_parity_with_gc_path(rng, S, field, garbler):
    """THE satellite contract: the 1-of-2^S whole-level flow is
    BIT-IDENTICAL to the GC whole-level flow — not just the
    reconstructed [x == y] but both sides' additive shares (same
    b2a seed -> same r0/r1 stream), for S ∈ {2, 4, 6} on FE62 and F255,
    whichever side garbles."""
    B = 30
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    flip = rng.integers(0, 2, size=B).astype(bool)
    y[flip, rng.integers(0, S, size=B)[flip]] ^= True
    eq = np.all(x == y, axis=1)
    gs = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    bs = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    shares = {}
    for path in ("ot2s", "gc"):
        snd, rcv = otext.inprocess_pair()
        u, t, idx0 = secure.ev_step1_fused(rcv, y)
        msg, v_gb = secure.gb_step_level(
            snd, np.asarray(u), x, gs, bs, field, garbler, path=path
        )
        v_ev = secure.ev_open_level(
            t, y, np.asarray(msg), B, S, field, idx0, path=path
        )
        v0, v1 = (v_gb, v_ev) if garbler == 0 else (v_ev, v_gb)
        diff = np.asarray(field.canon(field.sub(v0, v1)))
        if field is F255:
            np.testing.assert_array_equal(diff[:, 0], eq.astype(np.uint32))
            assert not diff[:, 1:].any()
        else:
            np.testing.assert_array_equal(diff, eq.astype(np.uint64))
        shares[path] = (
            np.asarray(field.canon(v0)), np.asarray(field.canon(v1))
        )
    np.testing.assert_array_equal(shares["ot2s"][0], shares["gc"][0])
    np.testing.assert_array_equal(shares["ot2s"][1], shares["gc"][1])


@pytest.mark.parametrize("path", ["ot2s", "gc"])
def test_mid_level_idx_offset_continuity(rng, path):
    """Two successive whole-level batches on ONE extension session: the
    pad index base advances with the consumed counter, so identical
    inputs produce different wire bytes (no pad reuse) while both
    batches open correctly — the mid-level continuity the sharded /
    multi-level crawl depends on."""
    field = FE62
    B, S = 20, 4
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    y[::4] = ~y[::4]
    eq = np.all(x == y, axis=1)
    gs = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    bs = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    snd, rcv = otext.inprocess_pair()
    msgs = []
    for batch in range(2):
        u, t, idx0 = secure.ev_step1_fused(rcv, y)
        assert idx0 == batch * B * S  # the counter IS the index base
        msg, v0 = secure.gb_step_level(
            snd, np.asarray(u), x, gs, bs, field, 0, path=path
        )
        v1 = secure.ev_open_level(
            t, y, np.asarray(msg), B, S, field, idx0, path=path
        )
        diff = np.asarray(field.canon(field.sub(v0, v1)))
        np.testing.assert_array_equal(diff, eq.astype(np.uint64))
        msgs.append(np.asarray(msg))
    assert snd.consumed == rcv.consumed == 2 * B * S
    # same inputs, same seeds — but a moved index base: every pad (and
    # with it the wire) must differ, or batch 2 would reuse batch 1's
    assert not np.array_equal(msgs[0], msgs[1])


# ---------------------------------------------------------------------------
# Socket flow: whole-level crawl, phase split, 2-dim oracle, warm compile
# ---------------------------------------------------------------------------


def _cfg(port_base, **kw):
    # f_max=8 keeps the warmup ladder (and with it the per-bucket compile
    # space these tests pay on XLA:CPU) to four rungs; the crawls here
    # never outgrow it
    defaults = dict(
        data_len=5,
        n_dims=1,
        ball_size=1,
        addkey_batch_size=8,
        num_sites=4,
        threshold=0.2,
        zipf_exponent=1.03,
        server0=f"127.0.0.1:{port_base}",
        server1=f"127.0.0.1:{port_base + 10}",
        distribution="zipf",
        f_max=8,
        secure_exchange=True,
    )
    defaults.update(kw)
    return Config(**defaults)


def _client_keys(rng, L, n, d=1):
    pts = np.concatenate(
        [np.full((n - 4, d), 11), rng.integers(0, 1 << L, size=(4, d))]
    )
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    return pts_bits, ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


async def _run_crawl(cfg, port, k0, k1, nreqs=12, warmup=False):
    s0 = rpc.CollectorServer(0, cfg)
    s1 = rpc.CollectorServer(1, cfg)
    t1 = asyncio.create_task(
        s1.start("127.0.0.1", port + 10, "127.0.0.1", port + 11)
    )
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(
        s0.start("127.0.0.1", port, "127.0.0.1", port + 11)
    )
    await asyncio.gather(t0, t1)
    c0 = await rpc.CollectorClient.connect("127.0.0.1", port)
    c1 = await rpc.CollectorClient.connect("127.0.0.1", port + 10)
    lead = RpcLeader(cfg, c0, c1)
    await lead._both("reset")
    await lead.upload_keys(k0, k1)
    if warmup:
        await lead.warmup()
    res = await lead.run(nreqs)
    for c in (c0, c1):
        await c.aclose()
    return res, lead, (s0, s1)


def _crawl(cfg, port, k0, k1, **kw):
    async def go():
        res, lead, servers = await _run_crawl(cfg, port, k0, k1, **kw)
        for s in servers:
            await s.aclose()
        return res, lead, servers

    return asyncio.run(go())


def test_whole_level_crawl_phase_split_and_parity(rng):
    """The default secure crawl runs WHOLE-LEVEL (one GC/OT batch per
    level even with crawl_shard_nodes set, no pipeline telemetry), its
    results are bit-identical to the GC-path form, and the run report
    carries the full secure-kernel split: otext/b2a busy, garble/eval
    present-but-zero on the ot2s path, the ot_path counters, and the
    rolled-up ``secure_kernels`` section.  (Whole-level vs SHARDED
    secure parity is pinned by test_pipeline's secure leg.)"""
    L, n = 5, 12
    _, (k0, k1) = _client_keys(rng, L, n)
    res_whole, lead_w, servers = _crawl(
        _cfg(BASE_PORT, crawl_shard_nodes=1, crawl_pipeline_depth=3),
        BASE_PORT, k0, k1,
    )
    # whole-level collapsed the sharded pipeline: no pipeline telemetry
    assert lead_w.obs.timer_seconds("pipeline_overlap") == 0.0
    rep = obsreport.run_report(
        [lead_w.obs, servers[0].obs, servers[1].obs]
    )
    assert "pipeline" not in rep
    sk = rep["secure_kernels"]
    assert sk["ot_path"] == "ot2s"
    assert sk["levels_ot2s"] == 2 * L and sk["levels_gc"] == 0
    assert sk["otext_seconds"] > 0.0 and sk["b2a_seconds"] > 0.0
    assert sk["garble_seconds"] == 0.0 and sk["eval_seconds"] == 0.0
    for s in servers:  # all four phases materialized on BOTH registries
        phases = s.obs.report()["phases"]
        for name in ("otext", "garble", "eval", "b2a"):
            assert name in phases, name
    res_gc, _, gc_servers = _crawl(
        _cfg(BASE_PORT + 80, ot_path="gc"), BASE_PORT + 80, k0, k1
    )
    assert res_whole.counts.size  # real hitters: a real compare
    np.testing.assert_array_equal(res_whole.counts, res_gc.counts)
    np.testing.assert_array_equal(res_whole.paths, res_gc.paths)
    # the GC-path run reports its path + nonzero circuit phases
    rep_gc = obsreport.run_report([s.obs for s in gc_servers])
    assert rep_gc["secure_kernels"]["ot_path"] == "gc"
    assert rep_gc["secure_kernels"]["garble_seconds"] > 0.0
    assert rep_gc["secure_kernels"]["eval_seconds"] > 0.0


def test_two_dim_secure_crawl_matches_trusted_oracle(rng):
    """n_dims = 2 -> S = 4: the generalized 1-of-16 path through the
    full socket flow matches the trusted-mode driver bit-for-bit — the
    multi-dimensional crawl really does skip the garbled circuit."""
    L, n, d = 4, 12, 2
    pts_bits, (k0, k1) = _client_keys(rng, L, n, d=d)
    # 2^d-way branching needs frontier headroom past the 1-dim default
    cfg = _cfg(BASE_PORT + 120, data_len=L, n_dims=d, f_max=32)
    res, _, servers = _crawl(cfg, BASE_PORT + 120, k0, k1)
    rep = obsreport.run_report([s.obs for s in servers])
    assert rep["secure_kernels"]["ot_path"] == "ot2s"  # no GC engaged
    # the report's shape is the LAST level's; the widths are every
    # level's: the inner levels' two words beside the F255 leaf's eight
    assert (rep["secure_kernels"]["string_bits"],
            rep["secure_kernels"]["child_patterns"],
            rep["secure_kernels"]["payload_words"]) == (4, 4, [2, 8])
    got = {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(res.decode_ints(), res.counts)
    }
    s0, s1 = driver.make_servers(k0, k1)
    want_res = driver.Leader(
        s0, s1, n_dims=d, data_len=L, f_max=cfg.f_max
    ).run(nreqs=n, threshold=cfg.threshold)
    want = {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(want_res.decode_ints(), want_res.counts)
    }
    assert got == want and got


def test_warmed_secure_crawl_triggers_zero_fresh_compiles(rng):
    """THE warmup-completeness contract: after one warmed crawl has run,
    a second crawl over the same shapes triggers ZERO fresh XLA backend
    compiles (utils/compile_cache.backend_compiles).  Catches every
    per-batch recompile regression at once: a counter leaking into a
    static arg, a fresh jit wrapper per call, or a warmup hole in the
    fused otext/ot2s/gc program ladder (the OT counters, crawl counter,
    and session seeds all differ between the two crawls, so anything
    shape-stable that recompiles on VALUES fails here loudly)."""
    from fuzzyheavyhitters_tpu.utils import compile_cache

    L, n = 5, 12
    _, (k0, k1) = _client_keys(rng, L, n)
    res1, _, _ = _crawl(
        _cfg(BASE_PORT + 160), BASE_PORT + 160, k0, k1, warmup=True
    )
    before = compile_cache.backend_compiles()
    res2, _, _ = _crawl(
        _cfg(BASE_PORT + 200), BASE_PORT + 200, k0, k1, warmup=True
    )
    fresh = compile_cache.backend_compiles() - before
    np.testing.assert_array_equal(res1.counts, res2.counts)
    np.testing.assert_array_equal(res1.paths, res2.paths)
    assert fresh == 0, f"{fresh} fresh compiles in a fully-warmed crawl"
