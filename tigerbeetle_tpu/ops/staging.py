"""The ONE staging of a host batch for a commit program, on one chip and on
the mesh: a request travels as three packed buffers in one ``device_put``.

Every commit program takes ``(ledger, cols64, cols32, meta)`` and slices the
columns back out by name at entry (``unstage``).  The two executors differ
in WHERE the operands are placed and in nothing else: the sharded one passes
its replicated ``NamedSharding``, the one-chip one nothing (the default
device).  19 column transfers and two eager scalars a request cost a v5e
host 7.3 ms where the packed three cost 2.5 (PERF.md PR 38, PR 46), and each
eager scalar was a program of its own that the device waited for.

Counters, while metrics are on: ``stage.puts`` (``device_put`` calls) and
``stage.bytes`` (host bytes handed to them)."""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import numpy as np

from ..obs.metrics import registry as _obs


@functools.lru_cache(maxsize=None)
def staged_names(dtype: np.dtype) -> Tuple[tuple, tuple]:
    """A wire dtype's fields by staged width: the ``uint64`` columns, and the
    narrower ones (``uint32`` on the device: ``types.to_soa``'s widening)."""
    wide = tuple(n for n in dtype.names if dtype.fields[n][0] == np.uint64)
    return wide, tuple(n for n in dtype.names if n not in wide)


def _fill(cols64: np.ndarray, cols32: np.ndarray, batch: np.ndarray) -> None:
    n = len(batch)
    wide, narrow = staged_names(batch.dtype)
    for i, name in enumerate(wide):
        cols64[i, :n] = batch[name]
    for i, name in enumerate(narrow):
        cols32[i, :n] = batch[name]


def _put(host: tuple, sharding):
    if _obs.enabled:
        _obs.counter("stage.puts").inc()
        _obs.counter("stage.bytes").inc(sum(a.nbytes for a in host))
    return jax.device_put(host, sharding)


def stage_batch(batch: np.ndarray, lanes: int, timestamp: int, sharding=None):
    """Stage one host batch for a commit program: the operands every program
    takes after the ledger, put ONCE, on the default device or (``sharding``)
    already replicated on a mesh, so that the program's dispatch finds each
    one in place.

    Returns ``(cols64, cols32, meta)``: the batch's ``uint64`` columns as the
    rows of one ``uint64[14, lanes]`` buffer, its narrower ones as the rows
    of one ``uint32[5, lanes]``, both in the dtype's field order and zero
    beyond ``len(batch)`` (the pad contract the kernels rely on), and
    ``meta = uint64[2]`` = (count, timestamp).  The host arrays are fresh
    for each batch, so nothing can refill one under a transfer that still
    reads it (on XLA-CPU ``device_put`` may alias a numpy buffer zero-copy)."""
    n = len(batch)
    assert n <= lanes, "batch exceeds configured lanes"
    wide, narrow = staged_names(batch.dtype)
    cols64 = np.zeros((len(wide), lanes), np.uint64)
    cols32 = np.zeros((len(narrow), lanes), np.uint32)
    _fill(cols64, cols32, batch)
    meta = np.array([n, timestamp], np.uint64)
    return _put((cols64, cols32, meta), sharding)


def stage_group(
    batches: List[np.ndarray], lanes: int, timestamps: Sequence[int],
    rows: int,
):
    """Stage a grouped run for the one-chip loop program: ``stage_batch``'s
    operands with a leading dimension of ``rows`` >= ``len(batches)``,
    ``uint64[rows, 14, lanes]``, ``uint32[rows, 5, lanes]`` and ``meta =
    uint64[2, rows]`` (counts, then timestamps), in ONE ``device_put``.  The
    rows past the run are zero and their counts 0, where the loop stops; the
    caller picks ``rows`` from the run's length, so a short run uploads a
    short stack."""
    k = len(batches)
    assert 0 < k <= rows
    wide, narrow = staged_names(batches[0].dtype)
    cols64 = np.zeros((rows, len(wide), lanes), np.uint64)
    cols32 = np.zeros((rows, len(narrow), lanes), np.uint32)
    meta = np.zeros((2, rows), np.uint64)
    for j, batch in enumerate(batches):
        assert len(batch) <= lanes, "batch exceeds configured lanes"
        _fill(cols64[j], cols32[j], batch)
        meta[0, j] = len(batch)
    meta[1, :k] = timestamps
    meta[1, k:] = timestamps[-1]
    return _put((cols64, cols32, meta), None)


def unstage(dtype: np.dtype, cols64, cols32, meta):
    """Inside a program: ``stage_batch``'s operands back as (the batch's
    columns by name, count, timestamp), what the kernels' bodies take."""
    wide, narrow = staged_names(dtype)
    batch = {name: cols64[i] for i, name in enumerate(wide)}
    batch.update({name: cols32[i] for i, name in enumerate(narrow)})
    return batch, meta[0], meta[1]


def column_row(dtype: np.dtype, name: str) -> int:
    """The row of ``cols64`` (or of ``cols32``, for a narrow field) that holds
    the column ``name``: the packed order is this module's to know."""
    wide, narrow = staged_names(dtype)
    return wide.index(name) if name in wide else narrow.index(name)


def id_columns(staged: tuple, dtype: np.dtype):
    """``(id_lo, id_hi)`` of a staged batch, looked up by name.  On the host
    each is an eager slice, a program of its own: only for the blocking
    routes whose kernel hands no id columns back."""
    cols64 = staged[0]
    return (
        cols64[column_row(dtype, "id_lo")], cols64[column_row(dtype, "id_hi")]
    )


def staged(impl, dtype: np.dtype):
    """``impl(ledger, batch, count, timestamp, ...)`` as a program over
    ``stage_batch``'s operands: ``(ledger, cols64, cols32, meta, ...)``.  It
    keeps ``impl``'s name, which is the program's in a device trace."""
    def program(ledger, cols64, cols32, meta, *args, **kwargs):
        return impl(
            ledger, *unstage(dtype, cols64, cols32, meta), *args, **kwargs
        )

    program.__name__ = impl.__name__
    program.__qualname__ = impl.__qualname__
    return program
