"""Published device peaks and the work a search call has to do.

The least time of a scan is computed from the data alone (the library rows'
precursor masses and charges, and the queries'), so it reads the same
whatever backend, plan or block size carries the scan out:

* compute: every in-window (query, row) pair is one Hamming similarity of
  two ±1 vectors of ``dim`` entries, which is an int8 dot product of
  ``2 * dim`` operations;
* memory: every library row that lies in some query's window is read once
  (``dim / 8`` bytes), the query hypervectors are read once, and the
  results (six int32 arrays of ``top_k`` per query) are written once.

A pair or row is in window when its charge equals the query's and its
precursor mass lies within ``open_tol_da`` of the query's (float64, closed
interval).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Peaks(NamedTuple):
    int8_ops: float     # OP/s
    bf16_flops: float   # FLOP/s
    hbm_bw: float       # bytes/s
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        int8_ops=393e12, bf16_flops=197e12, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e": per chip 393 TOP/s '
               'int8, 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s'),
}


def peaks_for(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


class Work(NamedTuple):
    pairs: int          # in-window (query, row) pairs
    rows: int           # distinct library rows in some query's window
    n_queries: int


def window_work(row_pmz, row_charge, q_pmz, q_charge, open_tol_da: float
                ) -> Work:
    """Pairs and distinct rows inside the open window, by two binary
    searches per charge."""
    row_pmz = np.asarray(row_pmz, np.float64)
    row_charge = np.asarray(row_charge)
    q_pmz = np.asarray(q_pmz, np.float64)
    q_charge = np.asarray(q_charge)
    pairs = 0
    rows = 0
    for c in np.unique(q_charge):
        r = np.sort(row_pmz[row_charge == c])
        q = q_pmz[q_charge == c]
        lo = np.searchsorted(r, q - open_tol_da, side="left")
        hi = np.searchsorted(r, q + open_tol_da, side="right")
        pairs += int((hi - lo).sum())
        # distinct rows: union of the [lo, hi) index ranges
        mark = np.zeros(len(r) + 1, np.int64)
        np.add.at(mark, lo, 1)
        np.add.at(mark, hi, -1)
        rows += int((np.cumsum(mark)[:-1] > 0).sum())
    return Work(pairs=pairs, rows=rows, n_queries=int(q_pmz.shape[0]))


def scan_ops(w: Work, dim: int) -> float:
    return float(w.pairs) * 2 * dim


def scan_bytes(w: Work, dim: int, top_k: int) -> float:
    return float(w.rows + w.n_queries) * dim / 8 + w.n_queries * top_k * 6 * 4


def least_time(w: Work, dim: int, top_k: int, peaks: Peaks
               ) -> tuple[float, str]:
    """(seconds, bounding term) of the scan on a chip with ``peaks``."""
    t_c = scan_ops(w, dim) / peaks.int8_ops
    t_m = scan_bytes(w, dim, top_k) / peaks.hbm_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
