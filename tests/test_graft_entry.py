"""The driver's two entry points (``__graft_entry__.py``): ``entry()``
hands back a forward step that lowers and compiles, and
``dryrun_multichip(n)`` takes the served multi-chip shape — two sharded
``CollectorServer``s and an ``RpcLeader`` — through a trusted crawl and a
secure one on each equality path, on the virtual CPU devices.

Ports: the dry run listens on ``__graft_entry__._DRYRUN_PORT`` (19731,
+40 a lane, +10/+11 inside a lane): a range no file under tests/ binds.
"""

import pytest

import jax

import __graft_entry__ as graft


def test_entry_lowers_and_compiles(cpu_default):
    fn, args = graft.entry()
    # fhh-lint: disable=recompile-churn (the one compile IS the test: the entry point hands back an unjitted step)
    compiled = jax.jit(fn).lower(*args).compile()
    counts = compiled(*args)
    assert counts.shape == (1, 4)  # the root node's four child patterns


@pytest.mark.parametrize("n_devices", [2, 4, 8])
def test_dryrun_multichip_on_virtual_devices(n_devices, capsys):
    """1, 2 and 4 data devices a server: the unsharded pair, and the
    client axis cut in two and in four."""
    graft.dryrun_multichip(n_devices)
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and out[0].startswith("dryrun_multichip OK")
    assert f"x {n_devices // 2} data device(s)" in out[0]


@pytest.mark.parametrize(
    "n_devices,why",
    [(16, "need 16 CPU devices"), (3, "even device count")],
    ids=["more-than-there-are", "odd"],
)
def test_dryrun_multichip_refuses(cpu_devices, n_devices, why):
    """More devices than the eight there are (``cpu_devices`` holds the
    suite to eight), and a count two servers cannot halve."""
    with pytest.raises(AssertionError, match=why):
        graft.dryrun_multichip(n_devices)
