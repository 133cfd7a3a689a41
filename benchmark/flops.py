"""Operations and bytes of the measured programs, counted from shapes.

Owned by the benchmark, so that a rate or a roofline share is computed
the same way in every PR. Bytes are the least the program must move
(each input read once, each output written once): a lower bound, so a
roofline share computed from them cannot pass 100% unless the time is
short of the work.
"""

from __future__ import annotations

import json
import os

F32 = 4


def lu_flops(n: int) -> float:
    """LU factorization of an n x n matrix (the LAPACK/HPL count)."""
    return 2.0 / 3.0 * n ** 3


def lu_solve_flops(n: int, nrhs: int = 1) -> float:
    """Forward and back substitution with the packed factors."""
    return 2.0 * n ** 2 * nrhs


def dense_step_flops(n: int) -> float:
    """One HPL-style step: factor + solve of one right-hand side."""
    return lu_flops(n) + lu_solve_flops(n)


def trailing_update(n: int, v: int) -> tuple:
    """(flops, bytes) of every superstep's trailing update of a right-
    looking blocked LU with tile v: at step k the (n-(k+1)v)^2 block gets
    a rank-v update. Per step the block is read and written once and the
    two v-wide panels are read once."""
    flops = bytes_ = 0.0
    for k in range(n // v):
        m = n - (k + 1) * v
        if m <= 0:
            continue
        flops += 2.0 * m * m * v
        bytes_ += (2.0 * m * m + 2.0 * m * v) * F32
    return flops, bytes_


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; a kind not in the table is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table)})")
    return table[device_kind]


def roofline_share(flops: float, bytes_: float, seconds: float,
                   device_kind: str) -> float:
    """Least time at the published peaks over the measured time, in %."""
    pk = peaks(device_kind)
    least = max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
