"""Bytes-touched roofline model for the commit kernels.

No benchmark has measured today's kernels on the chip yet, so this module
does what a roofline does: bound what the kernels *must* cost on the target
part (one v5e chip) from first principles, for a later chip measurement to
be argued against.

Model: the ledger tables live in HBM (they are the only state that scales);
the 8192-lane batch working set (~a few hundred KiB) is VMEM-resident.  Per
batch the kernel's unavoidable HBM traffic is hash-probe reads, row writes,
and balance read-modify-writes against the tables, counted exactly from the
column dtypes in ops/state_machine.py.  Everything else (sorts, segment ops,
validation ladders) runs on the batch working set in VMEM and contributes
fixed per-dispatch overhead, not bandwidth.

Throughput prediction: tx/s = count / max(bytes/BW, T_overhead) — i.e. the
batch is EITHER bandwidth-bound or launch/ALU-overhead-bound.  At 8190-lane
batches the HBM bytes per batch are ~3-4 MB, which at v5e HBM bandwidth is
~4-5 us; per-dispatch overhead on TPU inside a fori_loop is of the same
order, so the model brackets the prediction with a pessimistic and an
optimistic overhead figure rather than pretending to one number.

Reference workload being modeled: create_transfers at batch_max = 8190
(src/tigerbeetle/benchmark_load.zig:13-17, src/constants.zig:203-204).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax.numpy as jnp

from ..ops import state_machine as sm

# v5e-1 (single chip) public datasheet figures.
V5E_HBM_GBPS = 819.0  # GB/s
V5E_HBM_GB = 16.0

# Per-dispatch overhead brackets for one fused commit-kernel iteration inside
# a jitted fori_loop on TPU (no host round-trip).  The fast kernel lowers to
# ~200 fused HLO ops over 8192-lane arrays; TPU scalar-core sequencing of
# that many small ops lands in the tens of microseconds.  The general kernel
# adds sorted ladders and a Jacobi fixpoint (~8 passes worst case).
OVERHEAD_US = {"fast": (10.0, 40.0), "general": (60.0, 240.0)}


def _row_bytes(cols: Dict[str, jnp.dtype]) -> int:
    return sum(jnp.dtype(d).itemsize for d in cols.values())


@dataclass
class KernelModel:
    name: str
    bytes_per_batch: int
    count: int

    def predict(self, hbm_gbps: float = V5E_HBM_GBPS):
        bw_s = self.bytes_per_batch / (hbm_gbps * 1e9)
        lo_us, hi_us = OVERHEAD_US[self.name]
        t_opt = max(bw_s, lo_us * 1e-6)
        t_pes = max(bw_s, hi_us * 1e-6)
        return {
            "bytes_per_batch": self.bytes_per_batch,
            "hbm_bound_us": round(bw_s * 1e6, 1),
            "tx_s_optimistic": round(self.count / t_opt),
            "tx_s_pessimistic": round(self.count / t_pes),
        }


def fast_kernel_model(count: int = 8190, load_factor: float = 0.5) -> KernelModel:
    """HBM bytes for one fast-path create_transfers batch (steady state).

    Traffic, per valid lane (ops/state_machine.py create_transfers_impl):
      - transfers-table duplicate probe: expected 1/(1-load) probes reading
        the 16-byte key (id_lo, id_hi);
      - transfers-table insert: key write (16 B) + all value columns;
      - two account probes (debit, credit): key reads at expected probes;
      - account validation gather: flags/ledger/code/timestamp per side;
      - balance read-modify-write: debits_posted/credits_posted u128 limbs
        read + written per side (segment-sum dedup means <= 2*count sides;
        we charge the worst case);
      - result-code write (u32).
    """
    probes = 1.0 / (1.0 - load_factor)
    key_b = 16
    t_value_b = _row_bytes(sm.TRANSFER_COLS)  # value cols incl. timestamp
    a_meta_b = 4 + 4 + 4 + 8  # flags, ledger, code, timestamp
    a_balance_b = 4 * 8  # one side's posted debit/credit u128 limbs
    per_lane = (
        probes * key_b          # dup probe
        + key_b + t_value_b     # insert
        + 2 * probes * key_b    # account probes
        + 2 * a_meta_b          # validation gather
        + 2 * 2 * a_balance_b   # balance RMW (read + write, both sides)
        + 4                     # result code
    )
    return KernelModel("fast", int(per_lane * count), count)


def general_kernel_model(count: int = 8190, load_factor: float = 0.5,
                         jacobi_passes: int = 3) -> KernelModel:
    """The fully-general kernel (ops/transfer_full.py) adds: pending-transfer
    gather for post/void, posted-table probe + fulfillment write, history
    append (worst case both sides), and re-reads account balances once per
    Jacobi pass over the in-batch dependency ladder."""
    base = fast_kernel_model(count, load_factor)
    probes = 1.0 / (1.0 - load_factor)
    pend_b = probes * 16 + _row_bytes(sm.TRANSFER_COLS)  # pending row gather
    posted_b = probes * 16 + 16 + _row_bytes(sm.POSTED_COLS)
    hist_b = _row_bytes(sm.HISTORY_COLS)
    a_balance_b = 4 * 8
    extra = (
        pend_b + posted_b + hist_b
        + (jacobi_passes - 1) * 2 * 2 * a_balance_b
    )
    return KernelModel(
        "general", base.bytes_per_batch + int(extra * count), count
    )


def report(count: int = 8190) -> dict:
    """The dict bench.py embeds in its JSON line."""
    fast = fast_kernel_model(count)
    general = general_kernel_model(count)
    return {
        "model": "tx_s = count / max(hbm_bytes/bw, overhead)",
        "chip": "v5e-1",
        "hbm_gbps": V5E_HBM_GBPS,
        "fast": fast.predict(),
        "general": general.predict(),
    }
