"""Resident key planes: the bulk upload written to its final place on
the server's chips batch by batch, as the batches arrive.

``RpcLeader.upload_keys`` says of every batch where it goes (the
collection's client count ``n`` and the batch's first row ``lo``), so a
server needs no host copy of a key plane: at the first batch
:class:`KeyPlanes` allocates the five leaves of an ``IbDcfKeyBatch`` at
``[n, ...]``, one buffer a chip of the session's mesh, and every batch
is written to rows ``[lo, hi)`` IN PLACE (:func:`_write_rows`: the
buffers are donated, so HBM never holds a second copy of a plane).  The
batch's host arrays are handed to the jitted call as flat views and
nothing here keeps them: JAX's own reference, which lasts until the
copy is done, is the last one, and a receive slab (``wire._Lease``)
goes back to the free list as soon as its rows are on the chip.  A
first touch of new memory costs 1 ms a MB on the chip's host (PERF.md
section 6, PR 29), which is what a server that kept every batch paid
for the whole upload, and again for the concatenate.

The one holder of host batches is a session that may have to re-place
the keys after a lost chip (``CollectorServer._mesh_recover``, which
needs a checkpoint directory): ``keep_host`` keeps ``(lo, batch)``
pairs, and :meth:`KeyPlanes.finish` writes them again, through the same
code, when the resident planes are gone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ..obs import devmem
from ..ops.ibdcf import IbDcfKeyBatch
from ..parallel.server_mesh import DATA


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(planes, batch, lo):
    """Rows ``[lo, lo + B)`` of every plane <- ``batch``.  The planes
    are donated and the update is their only use, so XLA writes the rows
    where the plane lies.  ``dynamic_update_slice`` CLAMPS a start that
    would run past the end: the caller has checked the rows.

    The batch's leaves come FLAT, ``[B, -1]``, and the chip gives them
    the plane's shape.  Measured (PERF.md section 6, PR 33): the
    one-chip upload took 21.1 s so and 26.9 s with rows handed over in
    the plane's own shape, though the calling thread pays the same
    either way, a quarter of a millisecond a leaf.  The likely reason,
    not measured: a plane does not lie on the chip as on the host (a
    key's 512 levels minor, ahead of a seed's 4 words; the bit planes'
    client axis second-minor), the runtime re-lays such rows before
    they cross, and their receive slabs stay out that long."""
    return jax.tree.map(
        lambda p, b: jax.lax.dynamic_update_slice_in_dim(
            p, b.reshape(b.shape[:1] + p.shape[1:]), lo, axis=0
        ),
        planes, batch,
    )


@functools.lru_cache(maxsize=None)
def _zero_planes(like: tuple, dev):
    """The program that allocates one chip's planes: one a chip, not one
    a leaf (the upload waits for it), and one for every upload of the
    same shapes (a second collection compiles nothing)."""
    # fhh-lint: disable=recompile-churn (built once a shape and chip: the lru_cache above is the wrapper's home)
    return jax.jit(
        lambda: IbDcfKeyBatch(*[jnp.zeros(s.shape, s.dtype) for s in like]),
        out_shardings=None if dev is None else SingleDeviceSharding(dev),
    )


class KeyPlanes:
    """One upload's key planes on their way to ``CollectionSession.keys``
    (see module doc).  ``devices`` are the chips that share the client
    axis, ``n`` a multiple of their count (``ServerMesh.bind``); ``None``
    for the session without a mesh: one uncommitted buffer on the
    process's default device, as ``jax.device_put(keys)`` gave."""

    def __init__(self, n: int, like: IbDcfKeyBatch, devices: tuple | None,
                 keep_host: bool):
        self.n = int(n)
        self.devices = devices
        k = 1 if devices is None else len(devices)
        self.shard_rows = self.n // k
        self._like = tuple(
            jax.ShapeDtypeStruct((self.shard_rows,) + np.shape(a)[1:], a.dtype)
            for a in like
        )
        self.nbytes = k * sum(
            int(np.prod(s.shape)) * s.dtype.itemsize for s in self._like
        )
        # rows of every batch that arrived, in order of arrival: what
        # finish() holds to "each row once"
        self.written: list = []
        self.host: list | None = [] if keep_host else None
        self._bufs: list | None = None  # one IbDcfKeyBatch a device
        self._allocate()

    def _allocate(self) -> None:
        self._bufs = [
            _zero_planes(self._like, dev)() for dev in (self.devices or (None,))
        ]

    @property
    def sealed(self) -> bool:
        """True once :meth:`finish` has handed the planes over: the
        next batch belongs to a new upload."""
        return self._bufs is None

    def host_nbytes(self) -> int:
        return sum(devmem.tree_nbytes(tuple(b)) for _, b in self.host or ())

    def add(self, lo: int, batch: IbDcfKeyBatch) -> int:
        """One batch, rows ``[lo, lo + B)``: dispatch only.  Returns
        the bytes placed: 0 for rows outside ``[0, n)``, which cannot be
        written and which :meth:`finish` names."""
        lo, rows = int(lo), int(np.shape(batch[0])[0])
        self.written.append((lo, lo + rows))
        if self.host is not None:
            self.host.append((lo, batch))
        if lo < 0 or lo + rows > self.n or rows == 0:
            return 0
        self._place(lo, batch)
        return devmem.tree_nbytes(tuple(batch))

    def _place(self, lo: int, batch: IbDcfKeyBatch) -> None:
        hi, per = lo + int(np.shape(batch[0])[0]), self.shard_rows
        for j in range(lo // per, (hi - 1) // per + 1):
            # the rows of this chip (a batch may straddle two), flat:
            # views of the batch, no copy
            a, b = max(lo, j * per), min(hi, (j + 1) * per)
            part = IbDcfKeyBatch(
                *[leaf[a - lo:b - lo].reshape(b - a, -1) for leaf in batch]
            )
            self._bufs[j] = _write_rows(
                self._bufs[j], part, np.int32(a - j * per)
            )

    def check_cover(self) -> None:
        """The ``delivery`` guarantee, "every uploaded key is counted
        once": the rows written are ``[0, n)``, each once."""
        faults, at = [], 0
        for lo, hi in sorted(self.written):
            if lo < 0 or hi > self.n:
                faults.append(f"rows [{lo}, {hi}) lie outside [0, {self.n})")
            if lo > at:
                faults.append(f"rows [{at}, {lo}) never arrived")
            elif lo < at and hi > lo:
                faults.append(f"rows [{lo}, {min(hi, at)}) arrived twice")
            at = max(at, hi)
        if at < self.n:
            faults.append(f"rows [{at}, {self.n}) never arrived")
        if faults:
            raise RuntimeError(
                f"key upload of {self.n} clients is not whole: "
                + "; ".join(faults[:4])
            )

    def finish(self, mesh=None) -> IbDcfKeyBatch:
        """The whole planes, resident: checks the cover, waits for the
        last write and hands the buffers over (on a mesh as global
        arrays over the per-chip buffers: nothing moves).  Called again
        after the planes were lost with their chip, it first writes the
        host batches anew."""
        self.check_cover()
        if self._bufs is None:
            if self.host is None:
                raise RuntimeError(
                    "key planes lost with their device and this session "
                    "kept no host copy (no checkpoint directory): "
                    "re-upload the keys"
                )
            self._allocate()
            for lo, batch in self.host:
                self._place(lo, batch)
        if mesh is None:
            (keys,) = self._bufs
        else:
            sharding = NamedSharding(mesh, P(DATA))
            keys = IbDcfKeyBatch(*[
                jax.make_array_from_single_device_arrays(
                    (self.n,) + leaves[0].shape[1:], sharding, list(leaves)
                )
                for leaves in zip(*self._bufs)
            ])
        self._bufs = None
        # fhh-lint: disable=host-sync-in-hot-loop (once per upload: resident, not queued — level 0's expand cannot start before)
        jax.block_until_ready(keys)
        return keys
