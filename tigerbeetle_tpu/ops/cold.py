"""Tiered transfers store: hot device window + cold host spill (round-2
VERDICT #6, BASELINE config 4: 10M accounts / 1B transfers on one chip).

1B transfer rows cannot live in one chip's HBM.  Old transfers are
append-only and only ever touched by id (duplicate-id exists checks,
post/void of an old pending, lookup_transfers) or by the query index (which
stores ids, not rows).  So:

- The device transfers table holds the HOT window.  At eviction time the
  oldest rows (by timestamp) leave the device: they are pulled to the host,
  appended to the cold store as immutable id-sorted runs (the forest's
  run discipline, lsm/compaction.zig's role), and the hot table is rebuilt
  without them.
- A device-resident BLOOM FILTER over all cold ids rides along with every
  commit dispatch: a lane whose id (or pending_id) misses the hot table but
  hits the filter sets FLAG_COLD and the kernel applies NOTHING; the
  per-lane mask of those hits comes back with the flags.  The host then
  resolves the FLAGGED lanes' ids against the cold store exactly
  (``ColdStore.lookup_arrays``: one vectorised binary search a run) — every
  true cold row is rehydrated into the hot table (``rehydrate``, one
  program of the batch's shape) — and re-dispatches the whole batch with a
  per-lane ``cold_checked`` mask so Bloom false positives cannot loop.  A
  false positive so costs one more dispatch of the whole batch, which is
  why the filter is sized per BATCH and not per id (docs/deploy.md).
  No false negatives: every cold id is in the filter, so exists-precedence
  stays exact.
- The filter's SHAPE is fixed at start (``start --cold-bloom-log2``): it is
  an argument of the general commit program, so a growth recompiles that
  program inside a request.  Past its design load (12 bits a cold id) it
  still grows, counted (``cold.bloom.grows``) and logged once.
- Queries and lookups resolve missing rows from the cold store by id on the
  host (the same vectorised search).

WHEN an eviction runs (``machine.evict_cold``), always on the serving thread
and always BETWEEN two batches, never inside one: (a) in a commit's growth
check, before the batch is staged, when the batch would take the hot table
past load 0.5 at its ceiling (``_grow_if_needed``: the usual site under
load); (b) right after a committed batch and at a checkpoint's capture
(``_maybe_evict_between_batches``), when rehydrated rows have taken the
table there.  Where it falls is a pure function of the committed op stream,
so crash replay and every replica evict at the same op.  The run file is
written and fsynced before the eviction returns; a checkpoint's
``cold_manifest`` then makes it part of the durable state.  What it holds
the serving thread for, span by span, is in docs/tracing.md
(``cold_evict``).
"""

from __future__ import annotations

import functools
import io
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types
from ..utils.fs import atomic_write
from ..vsr.checksum import checksum as _checksum
from . import hash_table as ht
from . import staging
from . import state_machine as sm

BLOOM_HASHES = 4


# ---------------------------------------------------------------------------
# Device Bloom filter (bit array as uint32 lanes)
# ---------------------------------------------------------------------------


def make_bloom(bits_log2: int) -> jax.Array:
    assert 10 <= bits_log2 <= 34
    return jnp.zeros(((1 << bits_log2) // 32,), jnp.uint32)


def _bloom_positions(id_lo, id_hi, n_bits: int):
    """BLOOM_HASHES bit positions per id (double hashing h1 + i*h2)."""
    from .. import u128

    h1 = u128.mix64(id_lo, id_hi)
    h2 = u128.mix64(id_hi ^ jnp.uint64(0x9E3779B97F4A7C15), id_lo) | jnp.uint64(1)
    mask = jnp.uint64(n_bits - 1)
    return [
        (h1 + jnp.uint64(i) * h2) & mask for i in range(BLOOM_HASHES)
    ]


def bloom_check_impl(bloom: jax.Array, id_lo: jax.Array, id_hi: jax.Array) -> jax.Array:
    """bool[N]: possibly-cold (no false negatives)."""
    n_bits = bloom.shape[0] * 32
    hit = jnp.ones(id_lo.shape, jnp.bool_)
    for pos in _bloom_positions(id_lo, id_hi, n_bits):
        word = (pos >> jnp.uint64(5)).astype(jnp.int64)
        bit = jnp.uint32(1) << (pos & jnp.uint64(31)).astype(jnp.uint32)
        hit = hit & ((bloom[word] & bit) != 0)
    return hit


bloom_check = jax.jit(bloom_check_impl)


def bloom_add_host(bloom_np: np.ndarray, id_lo: np.ndarray, id_hi: np.ndarray) -> None:
    """Host-side insertion (eviction is host-driven); mirrors the device
    hash exactly — verified by the differential test."""
    n_bits = bloom_np.shape[0] * 32

    def mix64(lo, hi):
        # EXACT mirror of u128.mix64 (splitmix64 finalizer over a xor-fold).
        with np.errstate(over="ignore"):
            x = (lo ^ (hi * np.uint64(0x9E3779B97F4A7C15))).astype(np.uint64)
            x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)).astype(np.uint64)
            x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)).astype(np.uint64)
            return x ^ (x >> np.uint64(31))

    h1 = mix64(id_lo, id_hi)
    h2 = mix64(id_hi ^ np.uint64(0x9E3779B97F4A7C15), id_lo) | np.uint64(1)
    for i in range(BLOOM_HASHES):
        pos = (h1 + np.uint64(i) * h2) & np.uint64(n_bits - 1)
        # numpy >= 1.25 runs `ufunc.at` through an indexed inner loop: 82 ns
        # a position at 4 x 4.2 M of them into a 64 MB filter, all of it
        # cache misses (a pass a bit over a byte view took five times that).
        np.bitwise_or.at(
            bloom_np, (pos >> np.uint64(5)).astype(np.int64),
            (np.uint32(1) << (pos & np.uint64(31)).astype(np.uint32)),
        )


# ---------------------------------------------------------------------------
# Cold store: immutable id-sorted runs on disk
# ---------------------------------------------------------------------------


def _safe_basename(name: str) -> bool:
    """Peer-supplied manifest names must be plain basenames — anything that
    could resolve outside the spill directory is rejected."""
    return bool(name) and os.path.basename(name) == name and name not in (".", "..")


class ColdStore:
    """Append-only spill of evicted transfer rows: each run is an id-sorted
    TRANSFER_DTYPE array in a .npy file (memmap-read); lookups binary-search
    every run, newest first; small runs merge when the count grows.

    Deterministic reservation (the FreeSet role, lsm/free_set.zig): run
    sequence numbers, row membership (timestamp-threshold eviction), row
    order (id sort), and merge points (MAX_RUNS) are all pure functions of
    the committed op stream and the ledger config — so replicas executing
    the same history materialize byte-identical run files under identical
    names, the property the reference gets from deterministically reserving
    grid blocks ahead of compaction.  Pinned by
    tests/test_cold_tier.py::TestDeterministicReservation."""

    MAX_RUNS = 8

    def __init__(self, directory: Optional[str]) -> None:
        self.directory = directory
        self.runs: List[np.ndarray] = []
        self.run_paths: List[str] = []
        # Whole-file AEGIS checksums, parallel to run_paths: pinned into the
        # checkpoint's cold_manifest so restart detects on-disk corruption
        # of evicted rows (the same checksum-chain discipline as the forest).
        self.run_checksums: List[int] = []
        # Files superseded by a merge: deletable only AFTER a checkpoint
        # superblock referencing the merged manifest is durable (the repo's
        # GC-after-superblock discipline) — gc() is that hook.
        self.garbage: List[str] = []
        # Run filenames carry a sequence number that NEVER reuses a value
        # present on disk: an old checkpoint's cold_manifest may reference
        # files this in-memory state no longer tracks (post-merge garbage,
        # or runs written after the checkpoint we restored to), and a name
        # collision would silently replace those bytes.
        self.next_seq = 0
        # path -> whole-file checksum memo: run files are immutable
        # (atomic_write never rewrites in place), so verify/load/locate
        # never need to hash the same bytes twice.  Entries drop at gc.
        self._path_checksums: Dict[str, int] = {}
        self._scan_next_seq()

    def _file_checksum_cached(self, path: str) -> Optional[int]:
        have = self._path_checksums.get(path)
        if have is not None:
            return have
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        have = _checksum(blob)
        self._path_checksums[path] = have
        return have

    def _scan_next_seq(self) -> None:
        if not self.directory or not os.path.isdir(self.directory):
            return
        for entry in os.listdir(self.directory):
            parts = entry.split("_")
            if parts[0] == "run" and len(parts) > 1 and parts[1].isdigit():
                self.next_seq = max(self.next_seq, int(parts[1]) + 1)

    def _ensure_dir(self) -> None:
        if self.directory and not os.path.isdir(self.directory):
            os.makedirs(self.directory, exist_ok=True)

    @property
    def count(self) -> int:
        return sum(len(r) for r in self.runs)

    def _sort_key(self, rows: np.ndarray):
        return np.lexsort((rows["id_lo"], rows["id_hi"]))

    def append_run(self, rows: np.ndarray) -> None:
        if len(rows) == 0:
            return
        rows = rows[self._sort_key(rows)]
        if self.directory:
            path, file_checksum = self._write_run_file(rows)
            self.runs.append(np.load(path, mmap_mode="r"))
            self.run_paths.append(path)
            self.run_checksums.append(file_checksum)
        else:
            self.runs.append(rows)
            self.run_paths.append("")
            self.run_checksums.append(0)
        if len(self.runs) > self.MAX_RUNS:
            self._merge_all()

    def _write_run_file(self, rows: np.ndarray) -> Tuple[str, int]:
        self._ensure_dir()
        path = os.path.join(
            self.directory, f"run_{self.next_seq:06d}_{len(rows)}.npy"
        )
        self.next_seq += 1
        buf = io.BytesIO()
        np.save(buf, rows)
        blob = buf.getvalue()
        atomic_write(path, blob)
        return path, _checksum(blob)

    def _merge_all(self) -> None:
        merged = np.concatenate([np.asarray(r) for r in self.runs])
        merged = merged[self._sort_key(merged)]
        old_paths = [p for p in self.run_paths if p]
        self.runs, self.run_paths, self.run_checksums = [], [], []
        if self.directory:
            path, file_checksum = self._write_run_file(merged)
            self.runs = [np.load(path, mmap_mode="r")]
            self.run_paths = [path]
            self.run_checksums = [file_checksum]
            # A checkpoint taken BEFORE this merge still references the old
            # files; defer their deletion to gc() (post-superblock).
            self.garbage.extend(p for p in old_paths if p != path)
        else:
            self.runs = [merged]
            self.run_paths = [""]
            self.run_checksums = [0]

    def gc(self, paths: Optional[List[str]] = None) -> None:
        """Delete superseded run files — call only after a checkpoint
        superblock NOT referencing them is durable.  ``paths`` restricts
        deletion to files already superseded when that checkpoint was
        captured (async checkpointing: files merged away AFTER the capture
        are still referenced by the captured manifest and must wait for
        the next checkpoint)."""
        doomed = set(self.garbage) if paths is None else (
            set(paths) & set(self.garbage)
        )
        for p in doomed:
            try:
                os.remove(p)
            except OSError:
                pass
        self.garbage = [p for p in self.garbage if p not in doomed]
        for p in doomed:
            self._path_checksums.pop(p, None)

    def clear(self) -> None:
        """Drop in-memory state (restore to a pre-eviction checkpoint);
        files stay on disk — they may be referenced by older checkpoints."""
        self.runs, self.run_paths, self.run_checksums = [], [], []
        self.garbage = []

    def lookup(self, id_lo: int, id_hi: int) -> Optional[np.void]:
        """Newest-first binary search across runs."""
        for run in reversed(self.runs):
            lo_col, hi_col = run["id_lo"], run["id_hi"]
            left, right = 0, len(run)
            while left < right:
                mid = (left + right) // 2
                m_hi, m_lo = int(hi_col[mid]), int(lo_col[mid])
                if (m_hi, m_lo) < (id_hi, id_lo):
                    left = mid + 1
                else:
                    right = mid
            if left < len(run) and int(hi_col[left]) == id_hi and (
                int(lo_col[left]) == id_lo
            ):
                return np.asarray(run[left])
        return None

    def lookup_arrays(
        self, id_lo: np.ndarray, id_hi: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """``(found bool[n], rows TRANSFER_DTYPE[n])`` for n ids at once,
        newest run first as ``lookup``: one binary search a run over its two
        key columns, every id advancing together (``log2(len(run))`` steps of
        a few vector operations; no Python loop over the ids).  A row that
        was not found is zero."""
        id_lo = np.ascontiguousarray(id_lo, dtype=np.uint64)
        id_hi = np.ascontiguousarray(id_hi, dtype=np.uint64)
        n = len(id_lo)
        found = np.zeros(n, dtype=bool)
        rows = np.zeros(n, dtype=types.TRANSFER_DTYPE)
        for run in reversed(self.runs):
            todo = np.flatnonzero(~found)
            if not len(todo):
                break
            lo_col, hi_col = run["id_lo"], run["id_hi"]
            q_lo, q_hi = id_lo[todo], id_hi[todo]
            left = np.zeros(len(todo), dtype=np.int64)
            right = np.full(len(todo), len(run), dtype=np.int64)
            while True:
                open_ = left < right
                if not open_.any():
                    break
                mid = (left + right) >> 1
                at = np.where(open_, mid, 0)
                m_lo, m_hi = lo_col[at], hi_col[at]
                less = (m_hi < q_hi) | ((m_hi == q_hi) & (m_lo < q_lo))
                left = np.where(open_ & less, mid + 1, left)
                right = np.where(open_ & ~less, mid, right)
            at = np.minimum(left, len(run) - 1)
            hit = (left < len(run)) & (lo_col[at] == q_lo) & (
                hi_col[at] == q_hi)
            if hit.any():
                rows[todo[hit]] = run[at[hit]]
                found[todo[hit]] = True
        return found, rows

    def lookup_many(self, ids: List[Tuple[int, int]]) -> Dict[Tuple[int, int], np.void]:
        if not ids:
            return {}
        found, rows = self.lookup_arrays(
            np.array([lo for lo, _hi in ids], dtype=np.uint64),
            np.array([hi for _lo, hi in ids], dtype=np.uint64),
        )
        return {ids[i]: rows[i] for i in np.flatnonzero(found)}

    def rebuild_bloom(self, bits_log2: int) -> np.ndarray:
        bloom = np.zeros(((1 << bits_log2) // 32,), np.uint32)
        for run in self.runs:
            bloom_add_host(
                bloom, np.asarray(run["id_lo"]), np.asarray(run["id_hi"])
            )
        return bloom

    def manifest(self) -> List[dict]:
        return [
            {
                "path": os.path.basename(p),
                "rows": int(len(r)),
                "checksum": f"{c:032x}",
            }
            for p, r, c in zip(self.run_paths, self.runs, self.run_checksums)
        ]

    def verify_manifest(self, manifest: List[dict]) -> List[Tuple[str, int]]:
        """(basename, checksum) of manifest entries whose file is missing or
        corrupt locally — a state-synced checkpoint references the
        RESPONDER's cold runs, which must be fetched before load_manifest
        can succeed (consensus cold-fetch over request_blocks)."""
        damaged = []
        for entry in manifest:
            name = entry["path"]
            if not _safe_basename(name):
                raise ValueError(f"unsafe cold-run manifest path: {name!r}")
            expect = int(entry.get("checksum", "0"), 16)
            path = os.path.join(self.directory or "", name)
            have = self._file_checksum_cached(path)
            if have is None or (expect and have != expect):
                damaged.append((entry["path"], expect))
            elif not expect and len(np.load(path, mmap_mode="r")) != entry["rows"]:
                damaged.append((entry["path"], expect))
        return damaged

    def locate_by_checksum(self, checksum: int) -> Optional[str]:
        """Responder lookup: an on-disk run file whose bytes hash to
        ``checksum`` (cold runs are content-addressed across replicas the
        same way forest files are).  Checks live runs first, then the rest
        of the spill directory — a checkpoint being synced may reference
        runs that a later merge moved to the garbage list (still on disk
        until the next gc)."""
        for path, have in zip(self.run_paths, self.run_checksums):
            if path and have == checksum:
                return path
        if not self.directory or not os.path.isdir(self.directory):
            return None
        for entry in os.listdir(self.directory):
            if not entry.startswith("run_"):
                continue
            path = os.path.join(self.directory, entry)
            if self._file_checksum_cached(path) == checksum:
                return path
        return None

    def install_file(self, basename: str, checksum: int, blob: bytes) -> bool:
        """Write fetched cold-run bytes under the manifest's name; False on
        a checksum mismatch or an unsafe name (corrupt/malicious peer — a
        path-traversing entry like '../x' must not escape the spill dir)."""
        if not _safe_basename(basename):
            return False
        if _checksum(blob) != checksum:
            return False
        assert self.directory, "cold install requires a directory"
        self._ensure_dir()
        path = os.path.join(self.directory, basename)
        atomic_write(path, blob)
        self._path_checksums[path] = checksum
        return True

    def load_manifest(self, manifest: List[dict]) -> None:
        assert self.directory, "cold store reload requires a directory"
        self.runs, self.run_paths, self.run_checksums = [], [], []
        for entry in manifest:
            path = os.path.join(self.directory, entry["path"])
            expect = int(entry.get("checksum", "0"), 16)
            if expect:
                # Memoized: a verify_manifest just before (the sync-install
                # path) already hashed these immutable files once.
                actual = self._file_checksum_cached(path)
                if actual is None:
                    raise FileNotFoundError(path)
                if actual != expect:
                    raise RuntimeError(
                        f"cold run corrupt: {path} (checksum mismatch)"
                    )
            run = np.load(path, mmap_mode="r")
            assert len(run) == entry["rows"], f"cold run truncated: {path}"
            self.runs.append(run)
            self.run_paths.append(path)
            self.run_checksums.append(expect)
        self._scan_next_seq()  # never reuse any on-disk name


# ---------------------------------------------------------------------------
# Eviction kernels
# ---------------------------------------------------------------------------


def size_class(count: int, floor: int = 1) -> int:
    """The static ``k`` of ``extract_evicted`` and ``drop_evicted`` for
    ``count`` rows: the power of two at or above it (at least ``floor``), so
    that a deployment's evictions share their programs whatever each one's
    exact counts."""
    return max(floor, 1 << max(0, count - 1).bit_length())


def _live(table: ht.Table) -> jax.Array:
    return ((table.key_lo != 0) | (table.key_hi != 0)) & ~table.tombstone


@functools.partial(jax.jit, static_argnames=("frac_num", "frac_den"))
def eviction_threshold(
    table: ht.Table, frac_num: int, frac_den: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(T, leaving, live)``: the timestamp T such that ~frac of the live
    rows have ts <= T, how many live rows have (what the extract will
    compact), and how many rows are live (the rest is what the rehash
    keeps): both programs' size classes are chosen from the two counts on
    the host.

    T is the ``k``-th smallest live timestamp, ``k = count * frac`` (what
    sorting them and reading ``order[k]`` gave), found by bisection on the
    VALUE: the least T with more than ``k`` timestamps at or under it, 64
    counting passes over the column.  No sort: the v5e compiler takes over a
    minute for one of 2^24 rows, and the sort itself longer than 64 passes."""
    live = _live(table)
    ts = jnp.where(live, table.cols["timestamp"], jnp.uint64(0xFFFFFFFFFFFFFFFF))
    k = (table.count * jnp.uint64(frac_num)) // jnp.uint64(frac_den)
    k = jnp.minimum(k, jnp.uint64(table.capacity - 1))

    def halve(_, bounds):
        lo, hi = bounds
        mid = lo + ((hi - lo) >> jnp.uint64(1))
        enough = jnp.sum((ts <= mid).astype(jnp.uint64)) > k
        return jnp.where(enough, lo, mid + jnp.uint64(1)), jnp.where(
            enough, mid, hi)

    threshold, _ = jax.lax.fori_loop(
        0, 64, halve, (jnp.uint64(0), jnp.uint64(0xFFFFFFFFFFFFFFFF)))
    leaving = jnp.sum((live & (ts <= threshold)).astype(jnp.uint64))
    return threshold, leaving, jnp.sum(live.astype(jnp.uint64))


def _compacted(table: ht.Table, mask: jax.Array, k: int):
    """The rows of the slots ``mask`` sets, in slot order, in the first lanes
    of ``k``: ``(lane holds a row bool[k], {column: [k]})`` with ``id_lo`` /
    ``id_hi`` for the key; zero beyond the count.  A running count places
    each row (``ht.compact_lanes``); no argsort."""
    idx = ht.compact_lanes(mask, k)
    held = idx < table.capacity
    at = jnp.where(held, idx, 0)
    cols = dict(table.cols, id_lo=table.key_lo, id_hi=table.key_hi)
    return held, {
        name: jnp.where(held, col[at], jnp.zeros((), col.dtype))
        for name, col in cols.items()
    }


@functools.partial(jax.jit, static_argnames=("k",))
def extract_evicted(table: ht.Table, threshold_ts: jax.Array, k: int):
    """Compact the rows with ts <= threshold into the first ``k`` lanes, in
    slot order, packed as ``ops/staging.py`` packs a batch of transfers.

    Returns ``(count, cols64 uint64[14, k], cols32 uint32[5, k])``: the
    TRANSFER_DTYPE fields by staged width in the dtype's order, zero beyond
    ``count``.  The caller pulls the two buffers in ONE ``device_get``
    (``rows_to_numpy``) and then rebuilds the table."""
    evict = _live(table) & (table.cols["timestamp"] <= threshold_ts)
    held, rows = _compacted(table, evict, k)
    wide, narrow = staging.staged_names(types.TRANSFER_DTYPE)

    def packed(names, dtype):
        return jnp.stack([rows[name].astype(dtype) for name in names])

    return (
        jnp.sum(held.astype(jnp.uint64)),
        packed(wide, jnp.uint64), packed(narrow, jnp.uint32),
    )


# Lanes a trip of drop_evicted's write: a scatter's executable grows with its
# indices on a v5e (35 scatters of 2^22 indices: 17 MB in the compile cache;
# of 2^19 in a loop: 1.7), and so does its run time, from a floor of 0.76 ms
# into a column of 2^24 slots.
_WRITE_LANES = 1 << 19


@functools.partial(jax.jit, static_argnames=("k",))
def drop_evicted(table: ht.Table, threshold_ts: jax.Array, k: int) -> ht.Table:
    """Rebuild the hot table without the evicted rows (fresh rehash — no
    tombstone debt): the kept rows (at most ``k``) compacted in slot order,
    claimed into an empty table of the same capacity in ONE claim and
    written ``_WRITE_LANES`` at a time.  The claim is
    ``ht.claim_slots_empty``: claim_slots' protocol with the lanes in the
    order the slots had, so each row lands where one claim over all the
    slots' lanes put it, without that claim's sort of 2^24 lanes (207 s to
    compile for a v5e; PERF.md section 5 has what the eviction's programs
    cost to compile and to run)."""
    keep = _live(table) & (table.cols["timestamp"] > threshold_ts)
    held, rows = _compacted(table, keep, k)
    rows["claimed"] = ht.claim_slots_empty(
        table.capacity, rows["id_lo"], rows["id_hi"], held)
    rows["held"] = held
    chunk = min(k, _WRITE_LANES)

    def write_chunk(i, fresh):
        part = {
            name: jax.lax.dynamic_slice_in_dim(col, i * chunk, chunk)
            for name, col in rows.items()
        }
        return ht.write_rows(
            fresh, part["id_lo"], part["id_hi"], part["claimed"],
            part["held"], {name: part[name] for name in table.cols},
        )

    kept = jnp.sum(held.astype(jnp.int32))
    return jax.lax.fori_loop(
        0, (kept + chunk - 1) // chunk, write_chunk,
        ht.make_table(
            table.capacity, {n: v.dtype for n, v in table.cols.items()}),
    )


def rows_to_numpy(n, cols64, cols32) -> np.ndarray:
    """Assemble ``extract_evicted``'s packed rows into a host TRANSFER_DTYPE
    array: one fetch of both buffers (19 columns went one by one), cut to
    the count on the host."""
    count, host64, host32 = jax.device_get(  # tblint: ignore[host-sync] eviction
        (n, cols64, cols32)
    )
    count = int(count)
    wide, narrow = staging.staged_names(types.TRANSFER_DTYPE)
    rows = np.zeros(count, dtype=types.TRANSFER_DTYPE)
    for i, name in enumerate(wide):
        rows[name] = host64[i, :count]
    for i, name in enumerate(narrow):
        rows[name] = host32[i, :count]
    return rows


def rehydrate_impl(
    table: ht.Table, batch: Dict[str, jax.Array], count: jax.Array,
    _timestamp: jax.Array, max_probe: int,
) -> Tuple[ht.Table, jax.Array]:
    """Insert the first ``count`` cold rows of ``batch`` (whole rows, their
    own timestamps) into the hot table, but for those already hot (an
    earlier rehydration: a key inserted twice would break the table's
    uniqueness).  Returns ``(table', rows inserted)``; an insert that ran
    out of probes sets ``table'.probe_overflow``."""
    lanes = batch["id_lo"].shape[0]
    valid = jnp.arange(lanes, dtype=jnp.int32) < count.astype(jnp.int32)
    look = ht.lookup(table, batch["id_lo"], batch["id_hi"], max_probe)
    fresh = valid & ~look.found
    cols = {name: batch[name].astype(dt) for name, dt in sm.TRANSFER_COLS.items()}
    out, _ = ht.insert(
        table, batch["id_lo"], batch["id_hi"], fresh, cols, max_probe
    )
    return out, jnp.sum(fresh.astype(jnp.int32))


# ``(table, *staging.stage_batch(rows, lanes, 0))``: one upload, one program
# of the batch's shape whatever the number of rows.
rehydrate = jax.jit(
    staging.staged(rehydrate_impl, types.TRANSFER_DTYPE),
    donate_argnames=("ledger",),  # the table: `staged` names its first operand
    static_argnames=("max_probe",),
)
