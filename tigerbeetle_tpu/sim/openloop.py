"""Deterministic open-loop production workload (ROADMAP open item 5).

Every fault domain so far was exercised by a uniform CLOSED loop: each sim
client waits for its reply before sending the next request, so offered load
self-throttles to cluster speed and the admission machinery never meets
realistic traffic.  This module generates the open-loop twin — arrivals
happen on a seeded schedule whether or not earlier requests completed:

- **Zipfian hot accounts**: transfers draw debit/credit from a shared
  account universe with probability ∝ 1/rank^s, so a handful of hot
  accounts dominate (the shape real payment traffic has);
- **configurable arrival process**: ``poisson`` (exponential
  inter-arrivals), ``uniform`` (fixed cadence + jitter), or ``burst``
  (arrival groups) at a configurable aggregate rate;
- **mixed operations**: plain transfers, two-phase pending → post/void
  chains (the follow-up rides a later arrival of the same session), and
  account lookups;
- **many client ids**: arrivals are spread over a configurable cohort
  (thousands of ids at scale — the sim default keeps it in the dozens so
  VOPR runs stay fast).

Everything is pre-generated at construction from ONE seed: the scripts are
a pure function of the constructor arguments, independent of cluster
timing, so a pinned VOPR seed replays bit-identically and two runs of the
same seed produce byte-identical traffic (asserted by
tests/test_byzantine.py).  The generator is the default traffic for the
byzantine and overload VOPR kinds (sim/vopr.py).
"""

from __future__ import annotations

import math
import random
from typing import List, Optional, Tuple

import numpy as np

from .. import types
from ..types import TransferFlags
from ..vsr import wire

# Id spaces far above WorkloadGen's sequential ids so open-loop traffic can
# coexist with the closed-loop clients in one cluster.
ACCOUNT_BASE = 1 << 32
TRANSFER_BASE = 1 << 40

ARRIVALS = ("poisson", "uniform", "burst", "diurnal")


class OpenLoopGen:
    """Pre-generates per-client request scripts; see module docstring."""

    def __init__(
        self,
        seed: int,
        n_clients: int = 24,
        hot_accounts: int = 96,
        zipf_s: float = 1.1,
        arrival: str = "poisson",
        rate: float = 1.0,
        start_tick: int = 30,
        horizon: int = 3000,
        batch: int = 4,
        two_phase_rate: float = 0.3,
        query_rate: float = 0.15,
        ledger: int = 1,
        code: int = 10,
        ledgers: int = 1,
        ledger_skew: float = 1.2,
    ) -> None:
        assert arrival in ARRIVALS, arrival
        assert ledgers >= 1 and hot_accounts >= 2 * ledgers, (
            "every ledger needs >= 2 accounts for transfer pairs"
        )
        self.seed = seed
        self.n_clients = n_clients
        self.hot_accounts = hot_accounts
        self.arrival = arrival
        self.rate = rate
        self.start_tick = start_tick
        self.horizon = horizon
        self.ledger = ledger
        self.code = code
        rng = np.random.default_rng(seed)

        # Zipf weights over the shared hot-account universe (rank 1 is the
        # hottest; shuffled so hotness is not correlated with id order).
        self.account_ids = [ACCOUNT_BASE + k for k in range(1, hot_accounts + 1)]
        self.ledgers = ledgers
        if ledgers == 1:
            # Single-ledger path: draw sequence byte-identical to the
            # pre-multi-ledger generator (pinned byzantine/overload/
            # catch-up seeds replay their exact traffic).
            ranks = np.arange(1, hot_accounts + 1, dtype=np.float64)
            weights = 1.0 / np.power(ranks, zipf_s)
            perm = rng.permutation(hot_accounts)
            self._zipf_p = (weights / weights.sum())[perm]
            self._groups = None
        else:
            # Multi-ledger/multi-currency skew: accounts split into one
            # contiguous group per ledger; ledgers themselves are Zipf
            # over ``ledger_skew`` (one dominant currency, a long tail),
            # and transfers stay WITHIN a ledger — cross-currency rows
            # would just be rejected noise.  Ledger numbers ride
            # ``ledger + g``, currency codes ``code + g``.
            lranks = np.arange(1, ledgers + 1, dtype=np.float64)
            lw = 1.0 / np.power(lranks, ledger_skew)
            self._ledger_p = lw / lw.sum()
            bounds = np.linspace(0, hot_accounts, ledgers + 1).astype(int)
            self._groups = []
            self._group_p = []
            global_p = np.zeros(hot_accounts, dtype=np.float64)
            for g in range(ledgers):
                lo, hi = int(bounds[g]), int(bounds[g + 1])
                ids = self.account_ids[lo:hi]
                ranks = np.arange(1, len(ids) + 1, dtype=np.float64)
                weights = 1.0 / np.power(ranks, zipf_s)
                perm = rng.permutation(len(ids))
                gp = (weights / weights.sum())[perm]
                self._groups.append(ids)
                self._group_p.append(gp)
                global_p[lo:hi] = gp * self._ledger_p[g]
            self._zipf_p = global_p  # zipf_skew()'s global view

        # Arrival schedule: (tick, client_index) pairs over the horizon.
        ticks = self._arrival_ticks(rng)
        assignments = rng.integers(0, n_clients, size=len(ticks))

        # Per-client scripts: (arrival_tick, Operation, body).  The account
        # universe is created up front by the first clients (one batch
        # each), then the open-loop stream proper begins.
        self.scripts: List[List[Tuple[int, wire.Operation, bytes]]] = [
            [] for _ in range(n_clients)
        ]
        self._seed_account_batches(rng)
        pending_by_client: List[List[int]] = [[] for _ in range(n_clients)]
        seq_by_client = [0] * n_clients
        for tick, ci in zip(ticks, assignments):
            ci = int(ci)
            draw = rng.random()
            if draw < query_rate:
                op, body = self._lookup_batch(rng, batch)
            elif pending_by_client[ci] and draw < query_rate + two_phase_rate:
                op, body = self._resolve_batch(
                    rng, ci, pending_by_client, seq_by_client
                )
            else:
                op, body = self._transfer_batch(
                    rng, ci, batch, pending_by_client, seq_by_client,
                    two_phase_rate,
                )
            self.scripts[ci].append((int(tick), op, body))
        self.total_requests = sum(len(s) for s in self.scripts)

    # -- schedule -------------------------------------------------------------

    def _arrival_ticks(self, rng) -> List[int]:
        out: List[float] = []
        t = float(self.start_tick)
        if self.arrival == "poisson":
            while t < self.horizon:
                t += rng.exponential(1.0 / self.rate)
                out.append(t)
        elif self.arrival == "uniform":
            step = 1.0 / self.rate
            while t < self.horizon:
                t += step * (0.5 + rng.random())
                out.append(t)
        elif self.arrival == "burst":  # groups of ~4 arrivals at 4x spacing
            while t < self.horizon:
                t += 4.0 / self.rate
                for _ in range(int(rng.integers(2, 7))):
                    out.append(t + float(rng.random()))
        else:  # diurnal: two day-cycles with midday burst clusters
            # Poisson thinning against a raised-cosine intensity (trough
            # ~= 10% of the mean rate, peak ~= 2.5x), plus a burst group
            # at each peak — the daily shape of production payment
            # traffic, which uniform arrival processes never stress.
            peak = 2.5 * self.rate
            span = max(1.0, (self.horizon - self.start_tick) / 2.0)
            while t < self.horizon:
                t += rng.exponential(1.0 / peak)
                phase = 2.0 * math.pi * (t - self.start_tick) / span
                lam = self.rate * (
                    0.1 + 2.4 * (0.5 - 0.5 * math.cos(phase)) ** 2
                )
                if rng.random() < lam / peak:
                    out.append(t)
            for day in range(2):
                mid = self.start_tick + span * (day + 0.5)
                for _ in range(int(rng.integers(6, 14))):
                    out.append(mid + float(rng.normal(0.0, span * 0.02)))
        return [
            int(x) for x in out if self.start_tick <= x < self.horizon
        ]

    # -- batch builders -------------------------------------------------------

    def _seed_account_batches(self, rng) -> None:
        """The universe's create_accounts batches, spread over the first
        clients so one session's pipeline does not serialize the setup."""
        per = 32
        chunks = [
            self.account_ids[i : i + per]
            for i in range(0, len(self.account_ids), per)
        ]
        for i, chunk in enumerate(chunks):
            rows = [
                types.account(
                    id=a, ledger=self._ledger_of(a), code=self._code_of(a),
                    user_data_64=int(rng.integers(0, 1 << 32)),
                )
                for a in chunk
            ]
            ci = i % self.n_clients
            self.scripts[ci].append((
                self.start_tick + i,
                wire.Operation.create_accounts,
                types.accounts_array(rows).tobytes(),
            ))

    def _ledger_of(self, account_id: int) -> int:
        if self._groups is None:
            return self.ledger
        for g, ids in enumerate(self._groups):
            if account_id in ids:
                return self.ledger + g
        raise KeyError(account_id)

    def _code_of(self, account_id: int) -> int:
        return self.code + (self._ledger_of(account_id) - self.ledger)

    def _pick_pair(self, rng) -> Tuple[int, int, int, int]:
        """(debit, credit, ledger, code) — single-ledger keeps the legacy
        one-draw sequence; multi-ledger draws the ledger first so pairs
        stay within one currency."""
        if self._groups is None:
            dr, cr = rng.choice(
                len(self.account_ids), size=2, replace=False, p=self._zipf_p
            )
            return (
                self.account_ids[int(dr)], self.account_ids[int(cr)],
                self.ledger, self.code,
            )
        g = int(rng.choice(self.ledgers, p=self._ledger_p))
        ids = self._groups[g]
        dr, cr = rng.choice(
            len(ids), size=2, replace=False, p=self._group_p[g]
        )
        return ids[int(dr)], ids[int(cr)], self.ledger + g, self.code + g

    def _transfer_batch(
        self, rng, ci, batch, pending_by_client, seq_by_client,
        two_phase_rate,
    ) -> Tuple[wire.Operation, bytes]:
        rows = []
        for _ in range(batch):
            seq_by_client[ci] += 1
            tid = TRANSFER_BASE + ci * 1_000_000 + seq_by_client[ci]
            dr, cr, ledger, code = self._pick_pair(rng)
            flags = 0
            timeout = 0
            if rng.random() < two_phase_rate:
                flags = int(TransferFlags.PENDING)
                timeout = int(rng.integers(0, 20))
                pending_by_client[ci].append(tid)
                del pending_by_client[ci][:-16]
            rows.append(types.transfer(
                id=tid, debit_account_id=dr, credit_account_id=cr,
                amount=int(rng.integers(1, 1 << 24)), timeout=timeout,
                ledger=ledger, code=code, flags=flags,
                user_data_64=int(rng.integers(0, 1 << 16)),
            ))
        return (
            wire.Operation.create_transfers,
            types.transfers_array(rows).tobytes(),
        )

    def _resolve_batch(
        self, rng, ci, pending_by_client, seq_by_client
    ) -> Tuple[wire.Operation, bytes]:
        """Second phase of a two-phase chain: post or void an own pending
        transfer (posting one that already resolved/expired is VALID
        workload — the predictable failure codes audit like any other)."""
        pid = pending_by_client[ci].pop(
            int(rng.integers(0, len(pending_by_client[ci])))
        )
        seq_by_client[ci] += 1
        tid = TRANSFER_BASE + ci * 1_000_000 + seq_by_client[ci]
        flag = (
            TransferFlags.POST_PENDING_TRANSFER
            if rng.random() < 0.7
            else TransferFlags.VOID_PENDING_TRANSFER
        )
        dr, cr, ledger, code = self._pick_pair(rng)
        rows = [types.transfer(
            id=tid, debit_account_id=dr, credit_account_id=cr,
            amount=0, pending_id=pid, ledger=ledger, code=code,
            flags=int(flag),
        )]
        return (
            wire.Operation.create_transfers,
            types.transfers_array(rows).tobytes(),
        )

    def _lookup_batch(self, rng, batch) -> Tuple[wire.Operation, bytes]:
        picks = rng.choice(
            len(self.account_ids), size=min(batch, 4), replace=False,
            p=self._zipf_p,
        )
        arr = np.zeros(2 * len(picks), dtype="<u8")
        for i, k in enumerate(picks):
            a = self.account_ids[int(k)]
            arr[2 * i] = a & 0xFFFF_FFFF_FFFF_FFFF
            arr[2 * i + 1] = a >> 64
        return wire.Operation.lookup_accounts, arr.tobytes()

    # -- cluster attachment ---------------------------------------------------

    def attach(self, cluster, seed_salt: int = 0) -> List[int]:
        """Create one OpenLoopClient per non-empty script and register them
        with ``cluster`` (ids from a dedicated stream, like flood cohorts:
        attaching never shifts base-client schedules)."""
        from .cluster import OpenLoopClient

        ids = []
        for ci, script in enumerate(self.scripts):
            if not script:
                continue
            cid = ((self.seed ^ 0x09E7) * 1000 + 29 * (ci + 1)) | 1
            client = OpenLoopClient(
                client_id=cid,
                cluster_id=cluster.cluster_id,
                n_replicas=cluster.n,
                seed=(self.seed ^ 0x09E7) * 77 + ci + seed_salt,
                script=sorted(script, key=lambda e: e[0]),
            )
            cluster.clients[cid] = client
            cluster._wire_client(client)
            ids.append(cid)
        return ids


def zipf_skew(gen: OpenLoopGen) -> float:
    """Fraction of transfer rows touching the top-10% hottest accounts —
    the sweep's one-number skew witness (uniform traffic ≈ 0.1)."""
    hot = set()
    order = np.argsort(-gen._zipf_p)
    for k in order[: max(1, gen.hot_accounts // 10)]:
        hot.add(gen.account_ids[int(k)])
    touches = 0
    hot_touches = 0
    for script in gen.scripts:
        for _tick, op, body in script:
            if op != wire.Operation.create_transfers:
                continue
            rows = np.frombuffer(body, dtype=types.TRANSFER_DTYPE)
            for r in rows:
                for field in ("debit_account_id", "credit_account_id"):
                    a = int(r[field + "_lo"]) | (int(r[field + "_hi"]) << 64)
                    touches += 1
                    hot_touches += a in hot
    return hot_touches / max(1, touches)
