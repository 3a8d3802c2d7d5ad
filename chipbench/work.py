"""Least work of an ALS sweep, counted from its shapes, and the chip peaks
that bound how fast it can be done.

The counts are of the algorithm, not of any implementation: float32 at the
unpadded rank ``R``, every nonzero touched once per pass. Per nonzero of an
order-``N`` tensor:

* a CG matvec ``(G + lam I) x`` (implicit: a TTTP then an MTTKRP) reads
  the ``N`` coordinates and the weight, gathers ``N`` factor rows (the
  ``N - 1`` fixed factors and ``x``) and scatters one output row:
  ``4N + 4 + 4NR + 4R`` bytes, ``2NR + 1`` flops (``NR`` for the inner
  product over the gathered rows, ``(N - 1) R + R`` to scale and add the
  output row, one for the weight).
* the right-hand-side MTTKRP per mode reads the coordinates and the value,
  gathers ``N - 1`` rows and scatters one: ``4N + 4 + 4NR`` bytes,
  ``NR`` flops.

A sweep runs, per mode, one right-hand-side MTTKRP and at most
``cg_iters + 1`` matvecs (the initial residual and one per CG step). The
solver stops early at its tolerance and does not report how many steps it
took, so the sweep is counted at that bound. The dense CG vector updates
over the factor rows are left out. The count is therefore an upper bound on
the matvec passes and a lower bound on everything else.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def matvec_work(nnz: int, order: int, rank: int) -> tuple:
    """``(bytes, flops)`` of one CG matvec over ``nnz`` nonzeros."""
    n, r = order, rank
    return (nnz * F32 * (n + 1 + n * r + r), nnz * (2 * n * r + 1))


def rhs_work(nnz: int, order: int, rank: int) -> tuple:
    """``(bytes, flops)`` of one right-hand-side MTTKRP."""
    n, r = order, rank
    return (nnz * F32 * (n + 1 + n * r), nnz * n * r)


def sweep_work(nnz: int, order: int, rank: int, cg_iters: int) -> tuple:
    """``(bytes, flops)`` of one ALS sweep at the CG step bound."""
    mb, mf = matvec_work(nnz, order, rank)
    rb, rf = rhs_work(nnz, order, rank)
    passes = cg_iters + 1
    return (order * (rb + passes * mb), order * (rf + passes * mf))


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(bytes_: float, flops: float, device_kind: str) -> tuple:
    """``(seconds, bound)``: the least time for the work on one chip and
    which peak binds it, ``"hbm"`` or ``"flops"``."""
    p = peaks(device_kind)
    t_mem = bytes_ / p["hbm_bytes_per_s"]
    t_flop = flops / p["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
