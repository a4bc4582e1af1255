"""Secure data-plane tests: the GC+OT 2PC level sans-IO (the whole-level
oracle trio, at widths and batches tests/test_secure_kernels.py does not
take: S = 33, the two sides of ``OT2S_MAX_S``, a partly filled planar
block), which engine a width gets (``secure.ot_path``), the string
extraction's equivalence with the trusted compare, and a full two-server
socket run in secure mode that must (a) match trusted-mode heavy hitters
bit-for-bit and (b) never send a packed share-bit tensor to the peer."""

import asyncio
import secrets as pysecrets

import numpy as np
import pytest

from fuzzyheavyhitters_tpu.ops import gc_pallas, ibdcf, otext
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


def _strings(rng, B, S):
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = x.copy()
    flip = rng.integers(0, 2, size=B).astype(bool)
    y[flip, rng.integers(0, S, size=B)[flip]] ^= True
    return x, y, np.all(x == y, axis=1)


def _level(ot_pair, x, y, field, garbler, path):
    """One whole level through the oracle trio (secure.ev_step1_fused +
    gb_step_level / ev_open_level), the wire crossing as host arrays:
    (v0, v1) in server order, and the sender's message."""
    snd, rcv = ot_pair
    B, S = x.shape
    gc_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    b2a_seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    u, t_rows, idx0 = secure.ev_step1_fused(rcv, y)
    msg, v_gb = secure.gb_step_level(
        snd, np.asarray(u), x, gc_seed, b2a_seed, field, garbler, path=path
    )
    msg = np.asarray(msg)
    v_ev = secure.ev_open_level(t_rows, y, msg, B, S, field, idx0, path=path)
    return ((v_gb, v_ev) if garbler == 0 else (v_ev, v_gb)), msg


def _assert_shares_open_to(field, v0, v1, eq):
    """v0 - v1 == [x == y] per test (the r1 - r0 = 1 trick, ref:
    collect.rs:439-471; F255 payloads ride two blocks — the BlockPair
    double OT of collect.rs:775-916)."""
    diff = np.asarray(field.canon(field.sub(v0, v1)))
    if field is F255:
        np.testing.assert_array_equal(diff[:, 0], eq.astype(np.uint32))
        assert not diff[:, 1:].any()
    else:
        np.testing.assert_array_equal(diff, eq.astype(np.uint64))


@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
def test_pipeline_sans_io(ot_pair, rng, field):
    """garble -> Δ-OT labels -> eval -> b2a on ``auto`` JUST PAST the
    table's ceiling (S = OT2S_MAX_S + 1: the first width the garbled
    circuit takes, which the size of the message shows): v0 - v1 ==
    [x == y] per test."""
    B, S = 16, secure.OT2S_MAX_S + 1
    x, y, eq = _strings(rng, B, S)
    (v0, v1), msg = _level(ot_pair, x, y, field, 0, "auto")
    W = secure.payload_words(field)
    assert msg.size == gc_pallas.packed_msg_words(B, S, W)
    _assert_shares_open_to(field, v0, v1, eq)


@pytest.mark.parametrize("field", [FE62, F255], ids=["FE62", "F255"])
@pytest.mark.parametrize("garbler", [0, 1])
def test_pipeline_fused_sans_io(ot_pair, rng, field, garbler):
    """The garbled-circuit level (b2a payloads under the GC output labels
    — one protocol round trip) on an odd AND-tree (S = 33): v0 - v1 ==
    [x == y] per test REGARDLESS of which side garbles (the r1 = r0 ± 1
    sign trick)."""
    B, S = 16, 33
    x, y, eq = _strings(rng, B, S)
    (v0, v1), _ = _level(ot_pair, x, y, field, garbler, "gc")
    _assert_shares_open_to(field, v0, v1, eq)


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
    """The S = 2 fast path (1-of-4 chosen-payload OT — what ``auto`` picks
    for every 1-dim crawl) over a batch that is NOT a whole number of
    planar blocks (one block and 40 tests: the second block is mostly
    padding): v0 - v1 == [x == y] per test on both garbling sides."""
    B, S = gc_pallas.R_BLK * gc_pallas.GROUP + 40, 2
    x, y, eq = _strings(rng, B, S)
    (v0, v1), msg = _level(ot_pair, x, y, field, garbler, "auto")
    W = secure.payload_words(field)
    assert msg.size == (1 << S) * W * gc_pallas.padded_tests(B)
    _assert_shares_open_to(field, v0, v1, eq)


def test_ot4_receiver_learns_exactly_one_payload(ot_pair, rng):
    """1-of-2^S privacy shape, at the widest table ``auto`` builds (S =
    OT2S_MAX_S): opening with a WRONG choice (a string the receiver
    does not hold rows for) yields pad-garbage, not a payload — i.e. the
    table holds exactly one opening per receiver."""
    snd, rcv = ot_pair
    B, S = 32, secure.OT2S_MAX_S
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    y = rng.integers(0, 2, size=(B, S)).astype(bool)
    seed = np.frombuffer(pysecrets.token_bytes(16), "<u4")
    u, t_rows, idx0 = secure.ev_step1_fused(rcv, y)
    msg, _ = secure.gb_step_level(snd, np.asarray(u), x, seed, seed, FE62, 0)
    msg = np.asarray(msg)
    good = np.asarray(FE62.canon(
        secure.ev_open_level(t_rows, y, msg, B, S, FE62, idx0)
    ))
    bad = np.asarray(FE62.canon(
        secure.ev_open_level(t_rows, ~y, msg, B, S, FE62, idx0)
    ))
    # wrong-choice openings decrypt the wrong row with the wrong pad:
    # they must not reproduce the correct payloads (w.h.p.)
    assert (good != bad).sum() >= B - 1


def test_evaluator_share_is_masked(ot_pair, rng):
    """The evaluator's share alone must not reveal equality: with x == y
    in EVERY test it is still not a constant (each test's share is its
    own draw of the garbler's stream), and only the difference of the
    two servers' shares says 1."""
    B, S = 16, 33  # same shape as the garbled-circuit pipeline test
    x = rng.integers(0, 2, size=(B, S)).astype(bool)
    (v0, v1), _ = _level(ot_pair, x, x, FE62, 0, "gc")  # y == x: all equal
    _assert_shares_open_to(FE62, v0, v1, np.ones(B, bool))
    assert len(set(np.asarray(FE62.canon(v1)).tolist())) == B


@pytest.mark.parametrize(
    "S,override,want",
    [
        (1, "auto", "gc"),  # no table under two bits
        (secure.OT2S_MAX_S, "auto", "ot2s"),
        (secure.OT2S_MAX_S + 1, "auto", "gc"),
        (2, "gc", "gc"),  # the configuration turns the table off
        (secure.OT2S_MAX_S + 1, "ot2s", ValueError),  # loud, not 2^S wide
        (2, "ot4", ValueError),  # not a path
    ],
)
def test_ot_path_is_a_function_of_width_and_override(S, override, want):
    """``secure.ot_path``: both servers derive the level's engine from
    (S, ``Config.ot_path``) alone, so the wire format always agrees."""
    if want is ValueError:
        with pytest.raises(ValueError):
            secure.ot_path(S, override)
    else:
        assert secure.ot_path(S, override) == want


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


async def _run_protocol(cfg, keys0, keys1, nreqs, make_leader=RpcLeader,
                        probe=None):
    """The crawl's result; with ``probe``, what ``probe(leader, s0, s1)``
    read before the pair went down."""
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
    lead = make_leader(cfg, c0, c1)
    try:
        await asyncio.gather(c0.call("reset"), c1.call("reset"))
        await lead.upload_keys(keys0, keys1)
        res = await lead.run(nreqs)
        return res if probe is None else probe(lead, s0, s1)
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


@pytest.mark.parametrize("ot_path", ["auto", "gc"])
def test_secure_socket_run_matches_trusted(rng, monkeypatch, ot_path):
    """n_dims = 1 -> S = 2: ``Config.ot_path`` "auto" runs the 1-of-4 fast
    path (the production default), "gc" the garbled-circuit parity path,
    both through the full socket flow."""
    L, n = 5, 12
    port_base = BASE_PORT + (0 if ot_path == "auto" else 40)  # distinct ports per run
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

    cfg = _cfg(port_base=port_base, secure_exchange=True, ot_path=ot_path)
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


# ---------------------------------------------------------------------------
# The secure lane against the benchmark's plain reference, level by level,
# with every level in eight chunks
# ---------------------------------------------------------------------------


def _plain_reference():
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "references", "linf_ball_1d.py",
    )
    spec = importlib.util.spec_from_file_location("linf_ball_1d", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _TapLeader(RpcLeader):
    """The leader with a tap on its level loop: what it holds after each
    level (benchmark/lane.py does the same for the harness)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.held = []

    async def _run_one_level(self, level, nreqs, thresh):
        counts, alive = await super()._run_one_level(level, nreqs, thresh)
        if counts is not None:
            self.held.append((int(level), self.paths.copy(), counts.copy()))
        return counts, alive


@pytest.mark.parametrize("seed", [20260930, 3735928559])
def test_secure_lane_in_eight_chunks_matches_the_plain_reference(monkeypatch, seed):
    """``CollectorServer`` x2 + ``RpcLeader`` over sockets, secure lane,
    4,096 clients and a frontier bucket pinned at 8, so that every level
    (the F255 leaf level too) crosses in K = 8 chunks of one planar
    block: the frontier and the counts held after every level are those
    of ``benchmark/references/linf_ball_1d.py`` on the same seeded
    points."""
    from fuzzyheavyhitters_tpu.ops import gc_pallas

    L, n, ball = 5, 4096, 1
    rng = np.random.default_rng(seed)
    hot = rng.integers(0, 1 << L, size=3)
    pts = np.concatenate([
        rng.choice(hot, size=n - n // 4, p=[0.5, 0.3, 0.2]),
        rng.integers(0, 1 << L, size=n // 4),
    ])
    bits = np.array([[bitutils.int_to_bits(L, int(v))] for v in pts])
    k0, k1 = ibdcf.gen_l_inf_ball(bits, ball, rng, engine="np")
    block = gc_pallas.R_BLK * gc_pallas.GROUP
    monkeypatch.setattr(secure, "CHUNK_FRAME_BYTES", block * 32)
    cfg = _cfg(port_base=BASE_PORT + 80 + 40 * (seed % 2), data_len=L,
               ball_size=ball, threshold=0.1, addkey_batch_size=1024, f_max=32,
               secure_exchange=True)

    def probe(lead, s0, s1):
        return lead.held, [
            [s._default().obs.counter_value("secure_chunks", level=lv)
             for lv in range(L)] for s in (s0, s1)
        ]

    held, chunks = asyncio.run(_run_protocol(
        cfg, k0, k1, n,
        make_leader=lambda *a: _TapLeader(*a, min_bucket=8), probe=probe,
    ))
    ref = _plain_reference()
    want = ref.frontiers(bits, ball, max(1, int(cfg.threshold * n)), L)
    assert [lv for lv, _, _ in held] == list(range(L))
    for lv, paths, counts in held:
        assert ref.crawl_frontier(paths, counts) == want[lv + 1], lv
    assert want[L]  # hitters came out
    assert chunks == [[8] * L, [8] * L]
