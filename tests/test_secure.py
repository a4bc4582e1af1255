"""Secure data-plane tests: the GC+OT 2PC pipeline sans-IO, the string
extraction's equivalence with the trusted compare, and a full two-server
socket run in secure mode that must (a) match trusted-mode heavy hitters
bit-for-bit and (b) never send a packed share-bit tensor to the peer."""

import asyncio
import secrets as pysecrets

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import gc, ibdcf, otext
from fuzzyheavyhitters_tpu.ops.fields import F255, FE62
from fuzzyheavyhitters_tpu.protocol import collect, driver, rpc, secure
from fuzzyheavyhitters_tpu.protocol.leader_rpc import RpcLeader
from fuzzyheavyhitters_tpu.utils import bits as bitutils
from fuzzyheavyhitters_tpu.utils.config import Config

import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    """All tests in this module run on the CPU backend (see conftest)."""
    yield


@pytest.fixture(scope="module")
def ot_pair():
    return otext.inprocess_pair()


@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
def test_pipeline_sans_io(ot_pair, rng, field):
    """garble -> Δ-OT labels -> eval -> b2a: v0 - v1 == [x == y] per test
    (the r1-r0=1 trick, ref: collect.rs:439-471; F255 payloads ride two
    blocks — the BlockPair double OT of collect.rs:775-916)."""
    snd, rcv = ot_pair
    B, S = 16, 33  # matches test_gc's delta shape -> shared compiles
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    flip = rng.integers(0, 2, size=B).astype(bool)
    y[flip, rng.integers(0, S, size=B)[flip]] ^= True
    eq = np.all(x == y, axis=1)

    gc_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    b2a_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    u, t_rows = secure.ev_step1(rcv, y)
    batch, mask = secure.gb_step1(snd, np.asarray(u), x, gc_seed)
    e = secure.ev_step2(batch, t_rows, B, S)
    np.testing.assert_array_equal(np.asarray(mask) ^ np.asarray(e), eq)
    u2, t2, idx0 = secure.ev_step3(rcv, np.asarray(e))
    c0, c1, v0 = secure.gb_step2(snd, np.asarray(u2), mask, b2a_seed, field)
    v1 = secure.ev_step4(rcv, t2, idx0, np.asarray(c0), np.asarray(c1), e, field)
    diff = np.asarray(field.canon(field.sub(v0, v1)))
    if field is F255:
        np.testing.assert_array_equal(diff[:, 0], eq.astype(np.uint32))
        assert not diff[:, 1:].any()
    else:
        np.testing.assert_array_equal(diff, eq.astype(np.uint64))


@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
@pytest.mark.parametrize("garbler", [0, 1])
def test_pipeline_fused_sans_io(ot_pair, rng, field, garbler):
    """The FUSED flow (b2a payloads under the GC output labels — one
    protocol round trip, secure.gb_step_fused/ev_open_fused): v0 - v1 ==
    [x == y] per test REGARDLESS of which side garbles (the r1 = r0 ± 1
    sign trick), exactly like the two-round flow it replaces."""
    snd, rcv = ot_pair
    B, S = 16, 33
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    flip = rng.integers(0, 2, size=B).astype(bool)
    y[flip, rng.integers(0, S, size=B)[flip]] ^= True
    eq = np.all(x == y, axis=1)

    gc_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    b2a_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    u, t_rows, idx0 = secure.ev_step1_fused(rcv, y)
    msg, v_gb = secure.gb_step_fused(
        snd, np.asarray(u), x, gc_seed, b2a_seed, field, garbler
    )
    v_ev = secure.ev_open_fused(rcv, t_rows, np.asarray(msg), B, S, field, idx0)
    v0, v1 = (v_gb, v_ev) if garbler == 0 else (v_ev, v_gb)
    diff = np.asarray(field.canon(field.sub(v0, v1)))
    want = eq.astype(np.uint64)
    if field is F255:
        np.testing.assert_array_equal(diff[:, 0], want.astype(np.uint32))
        assert not diff[:, 1:].any()
    else:
        np.testing.assert_array_equal(diff, want)


def test_gf128_double_linearity_and_carry():
    """gf128_double: shift-with-carry semantics and linearity over XOR —
    the properties the 1-of-4 pad-offset distinctness proof rests on."""
    rng = np.random.default_rng(7)
    x = rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32)
    y = rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32)
    dbl = lambda a: np.asarray(otext.gf128_double(a))
    # linearity: 2(x ^ y) == 2x ^ 2y
    np.testing.assert_array_equal(dbl(x ^ y), dbl(x) ^ dbl(y))
    # no-carry case: plain 128-bit left shift
    lo = np.array([[0x40000000, 1, 0x80000000, 0x3FFFFFFF]], np.uint32)
    np.testing.assert_array_equal(
        dbl(lo), [[0x80000000, 2, 0, 0x7FFFFFFF]]
    )
    # carry case: x^127 wraps to the reduction constant 0x87
    hi = np.zeros((1, 4), np.uint32)
    hi[0, 3] = 0x80000000
    np.testing.assert_array_equal(dbl(hi), [[0x87, 0, 0, 0]])
    # doubling is invertible (linear + injective on a sample)
    assert len({bytes(r) for r in dbl(x)}) == len(x)
    # {0, s, 2s, 3s} pairwise distinct for s != 0 — the 4 pad offsets
    s = rng.integers(1, 2**32, size=(1, 4), dtype=np.uint32)
    offs = [np.zeros((1, 4), np.uint32), s, dbl(s), s ^ dbl(s)]
    assert len({bytes(o[0]) for o in offs}) == 4


@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
@pytest.mark.parametrize("garbler", [0, 1])
def test_pipeline_ot4_sans_io(ot_pair, rng, field, garbler):
    """The S = 2 fast path (1-of-4 chosen-payload OT, secure.gb_step_ot4 /
    ev_open_ot4): v0 - v1 == [x == y] per test on both garbling sides —
    the same contract as the GC fused flow it replaces for 1-dim crawls."""
    snd, rcv = ot_pair
    B, S = 64, 2
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    flip = rng.integers(0, 2, size=B).astype(bool)
    y[flip, rng.integers(0, S, size=B)[flip]] ^= True
    eq = np.all(x == y, axis=1)

    b2a_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    u, t_rows, idx0 = secure.ev_step1_fused(rcv, y)
    msg, v_snd = secure.gb_step_ot4(
        snd, np.asarray(u), x, b2a_seed, field, garbler
    )
    v_rcv = secure.ev_open_ot4(
        rcv, t_rows, y, np.asarray(msg), B, field, idx0
    )
    v0, v1 = (v_snd, v_rcv) if garbler == 0 else (v_rcv, v_snd)
    diff = np.asarray(field.canon(field.sub(v0, v1)))
    want = eq.astype(np.uint64)
    if field is F255:
        np.testing.assert_array_equal(diff[:, 0], want.astype(np.uint32))
        assert not diff[:, 1:].any()
    else:
        np.testing.assert_array_equal(diff, want)


def test_ot4_receiver_learns_exactly_one_payload(ot_pair, rng):
    """1-of-4 privacy shape: decrypting with a WRONG choice (a string the
    receiver does not hold rows for) yields pad-garbage, not a payload —
    i.e. the table holds exactly one opening per receiver."""
    snd, rcv = ot_pair
    B = 32
    x = rng.integers(0, 2, size=(B, 2)).astype(bool)
    y = rng.integers(0, 2, size=(B, 2)).astype(bool)
    b2a_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    u, t_rows, idx0 = secure.ev_step1_fused(rcv, y)
    msg, _ = secure.gb_step_ot4(snd, np.asarray(u), x, b2a_seed, FE62, 0)
    good = np.asarray(FE62.canon(
        secure.ev_open_ot4(rcv, t_rows, y, np.asarray(msg), B, FE62, idx0)
    ))
    bad = np.asarray(FE62.canon(
        secure.ev_open_ot4(rcv, t_rows, ~y, np.asarray(msg), B, FE62, idx0)
    ))
    # wrong-choice openings decrypt the wrong row with the wrong pad:
    # they must not reproduce the correct payloads (w.h.p.)
    assert (good != bad).sum() >= B - 1


def test_evaluator_share_is_masked(ot_pair, rng):
    """The evaluator's GC output alone must not reveal equality: its share
    differs from the plaintext wherever the garbler's mask bit is set."""
    snd, rcv = ot_pair
    B, S = 16, 33  # same shape as the pipeline test (one garble program)
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    u, t_rows = secure.ev_step1(rcv, x)  # y == x: all equal
    gc_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    batch, mask = secure.gb_step1(snd, np.asarray(u), x, gc_seed)
    e = np.asarray(secure.ev_step2(batch, t_rows, B, S))
    m = np.asarray(mask)
    assert m.any() and not m.all()
    np.testing.assert_array_equal(e, ~m)  # eq=1 everywhere -> e = 1 ^ mask


def test_child_strings_match_pattern_masks(rng):
    """String equality on extracted per-pattern strings ⇔ the packed-mask
    compare used by the trusted path (same membership predicate)."""
    d = 2
    F, N = 5, 17
    p0 = rng.integers(0, 1 << (4 * d), size=(F, N), dtype=np.uint32)
    p1 = rng.integers(0, 1 << (4 * d), size=(F, N), dtype=np.uint32)
    # force some exact agreements
    p1[:, ::3] = p0[:, ::3]
    s0 = np.asarray(secure.child_strings(jnp.asarray(p0), d))  # [F,C,N,S]
    s1 = np.asarray(secure.child_strings(jnp.asarray(p1), d))
    eq_strings = np.all(s0 == s1, axis=-1)  # [F, C, N]
    masks = collect.pattern_masks(d)
    diff = p0 ^ p1
    eq_masks = (diff[:, None, :] & masks[None, :, None]) == 0
    np.testing.assert_array_equal(eq_strings, eq_masks)


def test_node_share_sums_gating(rng):
    vals = rng.integers(0, 100, size=(2, 2, 6)).astype(np.uint64)
    w = np.ones((2, 2, 6), bool)
    w[0, 0, 0] = False  # dead client contribution
    w[1, :, :] = False  # dead node
    out = np.asarray(secure.node_share_sums(FE62, jnp.asarray(vals), jnp.asarray(w)))
    assert out[0, 0] == vals[0, 0, 1:].sum()
    assert out[0, 1] == vals[0, 1].sum()
    assert not out[1].any()


# ---------------------------------------------------------------------------
# Full two-server socket run in secure mode (ref test shape:
# equalitytest.rs:222-266 — both roles in one process over a duplex pipe)
# ---------------------------------------------------------------------------

BASE_PORT = 21331


def _cfg(port_base=BASE_PORT, **kw):
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
        f_max=32,
    )
    defaults.update(kw)
    return Config(**defaults)


async def _run_protocol(cfg, keys0, keys1, nreqs):
    s0 = rpc.CollectorServer(0, cfg)
    s1 = rpc.CollectorServer(1, cfg)
    host0, port0 = cfg.server0.rsplit(":", 1)
    host1, port1 = cfg.server1.rsplit(":", 1)
    port0, port1 = int(port0), int(port1)
    peer_port = port1 + 1
    t1 = asyncio.create_task(s1.start(host1, port1, host1, peer_port))
    await asyncio.sleep(0.05)
    t0 = asyncio.create_task(s0.start(host0, port0, host1, peer_port))
    c0 = await rpc.CollectorClient.connect(host0, port0)
    c1 = await rpc.CollectorClient.connect(host1, port1)
    await asyncio.gather(t0, t1)
    lead = RpcLeader(cfg, c0, c1)
    try:
        await asyncio.gather(c0.call("reset"), c1.call("reset"))
        await lead.upload_keys(keys0, keys1)
        return await lead.run(nreqs)
    finally:
        # a leaked listener (held alive by reference cycles until a gc
        # pass) keeps its port bound into LATER tests — close everything
        for c in (c0, c1):
            await c.aclose()
        for s in (s0, s1):
            await s.aclose()


def _client_keys(rng, L, n):
    pts = np.concatenate([np.full(n - 4, 11), rng.integers(0, 1 << L, size=4)])[
        :, None
    ]
    pts_bits = np.array(
        [[bitutils.int_to_bits(L, int(v)) for v in row] for row in pts]
    )
    return ibdcf.gen_l_inf_ball(pts_bits, 1, rng, engine="np")


@pytest.mark.parametrize("eq_ot4", [True, False], ids=["ot4", "gc"])
def test_secure_socket_run_matches_trusted(rng, monkeypatch, eq_ot4):
    """n_dims = 1 -> S = 2: runs the 1-of-4 fast path (the production
    default) AND the GC parity path through the full socket flow."""
    monkeypatch.setattr(secure, "EQ_OT4", eq_ot4)
    L, n = 5, 12
    port_base = BASE_PORT + (0 if eq_ot4 else 40)  # distinct ports per run
    k0, k1 = _client_keys(rng, L, n)

    # record every data/control-plane payload and every packed tensor
    sent, packed_tensors = [], []
    real_send = rpc._send
    real_expand = collect.expand_share_bits

    async def spy_send(writer, obj, **kw):
        sent.append(obj)
        await real_send(writer, obj, **kw)

    def spy_expand(keys, frontier, level, **kw):
        packed, children = real_expand(keys, frontier, level, **kw)
        packed_tensors.append(np.asarray(packed))
        return packed, children

    monkeypatch.setattr(rpc, "_send", spy_send)
    monkeypatch.setattr(collect, "expand_share_bits", spy_expand)

    cfg = _cfg(port_base=port_base, secure_exchange=True)
    res = asyncio.run(_run_protocol(cfg, k0, k1, n))
    got = {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(res.decode_ints(), res.counts)
    }

    # trusted-mode oracle (colocated driver)
    s0, s1 = driver.make_servers(k0, k1)
    want_res = driver.Leader(s0, s1, n_dims=1, data_len=L, f_max=cfg.f_max).run(
        nreqs=n, threshold=cfg.threshold
    )
    want = {
        tuple(int(v) for v in r): int(c)
        for r, c in zip(want_res.decode_ints(), want_res.counts)
    }
    assert got == want and got

    # no packed share-bit tensor ever crossed a socket
    assert packed_tensors
    def leaves(obj):
        if isinstance(obj, np.ndarray):
            yield obj
        elif isinstance(obj, (tuple, list)):
            for o in obj:
                yield from leaves(o)
        elif isinstance(obj, dict):
            for o in obj.values():
                yield from leaves(o)

    for obj in sent:
        for leaf in leaves(obj):
            for p in packed_tensors:
                assert not (
                    leaf.shape == p.shape and leaf.dtype == p.dtype
                    and np.array_equal(leaf, p)
                ), "packed share-bit tensor crossed the wire in secure mode"
