"""The controls of the comparison that decides ``correct``: each is a run of
the program with one guarantee of the configuration broken underneath, and
has to come out as NOT correct.  Not part of a benchmark run; run by hand on
the machine with the chip, at the cell's own size, on three seeds or more:

    python benchmark/control.py --workload <name> --seeds 1,2,3 --kind dup --seconds 10
    python benchmark/control.py --workload <name> --seeds 1,2,3 --kind lane --seconds 10

The system runs no model and states no precision, so a control breaks one
guarantee the configuration states, through ``run.run_cell``:

``dup``   delivery and exact counts.  On their way to the upload the keys of
          every second client take the place of their neighbour's, in both
          servers' batches: half the clients are counted twice and half
          never, the answer a sampling shortcut would give.  Same shapes,
          same programs; the run has to fail on ``levels_differing``.
``lane``  what the servers may learn.  The other exchange switched on
          (``secure_exchange`` flipped: for the secure cell the faster
          trusted swap); its counts are still exact, and the run has to fail
          on the lane's evidence counters.

Prints one JSON line a seed and exits 0 only if every seed came out not
correct.  ``tests/test_control.py`` keeps both at a size a test run holds.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

import run  # noqa: F401  (puts the checkout and this directory on sys.path)
import manifest


def every_second_client_twice(keys):
    """A key batch in which client 2i+1 holds client 2i's keys."""
    import jax
    import numpy as np

    return jax.tree.map(lambda x: np.repeat(np.asarray(x)[::2], 2, axis=0), keys)


def dup(cell, seed: int, seconds: float) -> dict:
    res = asyncio.run(run.run_cell(cell, seed, seconds, False,
                                   tamper_keys=every_second_client_twice))
    return {"seed": seed, "kind": "dup", "attempted": res["attempted"],
            "failed": res["failed"], "correct": res["correct"]}


def lane(cell, seed: int, seconds: float) -> dict:
    flipped = not cell.config["config"]["secure_exchange"]
    res = asyncio.run(run.run_cell(cell, seed, seconds, False,
                                   config_overrides={"secure_exchange": flipped}))
    return {"seed": seed, "kind": "lane", "secure_exchange": flipped,
            "attempted": res["attempted"], "failed": res["failed"],
            "correct": res["correct"]}


KINDS = {"dup": dup, "lane": lane}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--kind", choices=sorted(KINDS), required=True)
    p.add_argument("--seconds", type=float, default=10.0, help="window of a run")
    args = p.parse_args(argv)
    cell = manifest.cell(args.workload)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = KINDS[args.kind](cell, seed, args.seconds)
        print(json.dumps(rec), flush=True)
        all_failed &= not rec["correct"]
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
