"""XLA tile kernels behind a swappable backend registry.

Plays the role of the reference's `cosma::gemm` CBLAS shim
(`src/conflux/lu/blas.cpp:15-123`) and its LAPACKE calls (`cblas_dtrsm`,
`cblas_dgemm`, `LAPACKE_dgetrf`, `LAPACKE_dpotrf` — `conflux_opt.hpp:1346,
1537,1626`, `Cholesky.cpp:188`): every tile-level flop in the framework goes
through these entry points, so a Pallas backend can be swapped in without
touching algorithm code. Backends: 'xla' (default — let the compiler tile
onto the MXU) and 'pallas' (hand kernels for the hot ops, see
conflux_tpu/ops/pallas_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_BACKEND = "xla"
_VALID_BACKENDS = ("xla", "pallas")

# On TPU, float32 matmuls default to one bfloat16 MXU pass, which is far too
# coarse for factorization-grade accuracy (observed ~1e-2 LU residuals at
# N=1024). Dense linear algebra needs true float32 accumulation, so every
# matmul in this module pins HIGHEST precision; callers wanting the bf16 fast
# path opt in via set_matmul_precision.
_MATMUL_PRECISION = lax.Precision.HIGHEST


def set_matmul_precision(p) -> None:
    global _MATMUL_PRECISION
    _MATMUL_PRECISION = p


def matmul_precision():
    return _MATMUL_PRECISION


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in _VALID_BACKENDS:
        raise ValueError(f"unknown backend {name!r}; valid: {_VALID_BACKENDS}")
    if name == "pallas":
        # fail here, not at first use inside a trace
        import importlib

        importlib.import_module("conflux_tpu.ops.pallas_kernels")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


# --------------------------------------------------------------------------- #
# GEMM
# --------------------------------------------------------------------------- #


def gemm(a: jax.Array, b: jax.Array, c: jax.Array | None = None,
         alpha: float = 1.0, beta: float = 1.0,
         precision=None, backend: str | None = None) -> jax.Array:
    """alpha * a @ b (+ beta * c). The trailing-update hot op.

    On TPU the matmul runs on the MXU with float32 accumulation; XLA fuses
    the scale/add epilogue. Inputs keep their dtype (use bfloat16/float32
    for speed, float64 for the validation path).

    `precision` / `backend` default to the module-level settings **at trace
    time**; algorithm entry points resolve them outside jit and pass them as
    static arguments so they participate in the jit cache key.
    """
    backend = _BACKEND if backend is None else backend
    precision = _MATMUL_PRECISION if precision is None else precision
    if backend == "pallas":
        from conflux_tpu.ops import pallas_kernels

        out = pallas_kernels.gemm(a, b, precision=precision)
    else:
        out = jnp.matmul(
            a, b,
            preferred_element_type=_acc_dtype(a.dtype),
            precision=precision,
        )
        out = out.astype(a.dtype)
    if alpha != 1.0:
        out = alpha * out
    if c is not None:
        out = out + (beta * c if beta != 1.0 else c)
    return out


def _acc_dtype(dtype) -> jnp.dtype:
    """MXU accumulation dtype: float32 for narrow types, native otherwise."""
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dtype


def compute_dtype(dtype):
    """Dtype for panel factorizations and triangular solves.

    bfloat16 storage uses float32 panel math (LU/potrf kernels have no bf16
    path, and panel accuracy sets the factorization's accuracy); the trailing
    GEMMs stay in the storage dtype so bf16 runs ride the fast MXU path.
    """
    return _acc_dtype(dtype)


# --------------------------------------------------------------------------- #
# Triangular solves
# --------------------------------------------------------------------------- #


def trsm_left_lower_unit(L: jax.Array, B: jax.Array) -> jax.Array:
    """Solve L X = B with L unit lower triangular (A01 panel update,
    reference `conflux_opt.hpp:1537-1551`)."""
    return lax.linalg.triangular_solve(
        L, B, left_side=True, lower=True, unit_diagonal=True
    )


def trsm_right_upper(U: jax.Array, B: jax.Array) -> jax.Array:
    """Solve X U = B with U upper triangular (A10 panel update,
    reference `conflux_opt.hpp:1346-1359`)."""
    return lax.linalg.triangular_solve(
        U, B, left_side=False, lower=False, unit_diagonal=False
    )


def trsm_right_lower_t(L: jax.Array, B: jax.Array) -> jax.Array:
    """Solve X L^T = B with L lower triangular (Cholesky A10 update,
    reference `Cholesky.cpp:218-319` dtrsm). For complex dtypes the
    transpose is Hermitian (X L^H = B) — the A = L L^H convention."""
    return lax.linalg.triangular_solve(
        L, B, left_side=False, lower=True, transpose_a=True,
        conjugate_a=jnp.issubdtype(L.dtype, jnp.complexfloating),
        unit_diagonal=False
    )


def trsm_left_upper(U: jax.Array, B: jax.Array) -> jax.Array:
    """Solve U X = B with U upper triangular (LU back-substitution)."""
    return lax.linalg.triangular_solve(
        U, B, left_side=True, lower=False, unit_diagonal=False
    )


def trsm_left_upper_t(U: jax.Array, B: jax.Array) -> jax.Array:
    """Solve U^T X = B with U upper triangular (transpose-system solve,
    the getrs 'T' path)."""
    return lax.linalg.triangular_solve(
        U, B, left_side=True, lower=False, transpose_a=True,
        unit_diagonal=False
    )


def trsm_left_lower_unit_t(L: jax.Array, B: jax.Array) -> jax.Array:
    """Solve L^T X = B with L unit lower triangular."""
    return lax.linalg.triangular_solve(
        L, B, left_side=True, lower=True, transpose_a=True,
        unit_diagonal=True
    )


def blocked_trsm(T: jax.Array, B: jax.Array, *, lower: bool = True,
                 unit_diagonal: bool = False, dinv=None,
                 block_size: int | None = None, precision=None,
                 backend: str | None = None) -> jax.Array:
    """Blocked batched triangular solve (DESIGN §27): diagonal-block
    inverses + trailing-panel GEMMs instead of XLA's serial-per-row
    batched TriangularSolve — the vmapped serving programs' fast
    substitution path (`conflux_tpu.ops.batched_trsm`). T is (n, n) or
    (B, n, n) (packed factors fine); `backend='pallas'` (or the module
    backend, resolved at trace time like :func:`gemm`) routes batched
    operands through the Pallas kernel, interpret mode off-TPU."""
    from conflux_tpu.ops import batched_trsm

    backend = _BACKEND if backend is None else backend
    precision = _MATMUL_PRECISION if precision is None else precision
    return batched_trsm.blocked_trsm(
        T, B, lower=lower, unit_diagonal=unit_diagonal, dinv=dinv,
        block_size=block_size, precision=precision, backend=backend)


def batched_lu_factor(A: jax.Array, *, probe_w=None,
                      backend: str | None = None):
    """Batched pivoted LU of (B, n, n) systems (DESIGN §29): the coalesced
    factor lane's kernel entry point. `backend='pallas'` (or the module
    backend, resolved at trace time like :func:`gemm`) runs the
    batch-blocked Pallas kernel with the batch axis in the grid —
    interpret mode off-TPU; 'xla' vmaps `lax.linalg.lu`. Returns
    `(LU, perm)`, or `(LU, perm, wA)` with the in-kernel Freivalds probe
    row `wA = w^T A` when `probe_w` is given."""
    backend = _BACKEND if backend is None else backend
    if backend == "pallas":
        from conflux_tpu.ops import pallas_factor

        return pallas_factor.pallas_lu_factor_batched(A, probe_w=probe_w)
    lu_packed, _piv, perm = jax.vmap(lax.linalg.lu)(A)
    if probe_w is None:
        return lu_packed, perm
    wa = jnp.matmul(probe_w[None, None, :], A,
                    preferred_element_type=_acc_dtype(A.dtype),
                    precision=lax.Precision.HIGHEST)[:, 0, :]
    return lu_packed, perm, wa


def batched_cholesky_factor(A: jax.Array, *, probe_w=None,
                            backend: str | None = None):
    """Batched lower-Cholesky of (B, n, n) SPD systems (DESIGN §29).
    Backend semantics match :func:`batched_lu_factor`. Returns `L`, or
    `(L, wA)` when `probe_w` is given."""
    backend = _BACKEND if backend is None else backend
    if backend == "pallas":
        from conflux_tpu.ops import pallas_factor

        return pallas_factor.pallas_cholesky_factor_batched(
            A, probe_w=probe_w)
    L = lax.linalg.cholesky(A, symmetrize_input=False)
    if probe_w is None:
        return L
    wa = jnp.matmul(probe_w[None, None, :], A,
                    preferred_element_type=_acc_dtype(A.dtype),
                    precision=lax.Precision.HIGHEST)[:, 0, :]
    return L, wa


def trsm_left_lower(L: jax.Array, B: jax.Array) -> jax.Array:
    """Solve L X = B with L lower triangular (Cholesky forward solve)."""
    return lax.linalg.triangular_solve(
        L, B, left_side=True, lower=True, unit_diagonal=False
    )


def trsm_left_lower_t(L: jax.Array, B: jax.Array) -> jax.Array:
    """Solve L^T X = B with L lower triangular (Cholesky back solve).
    For complex dtypes the transpose is Hermitian (L^H X = B)."""
    return lax.linalg.triangular_solve(
        L, B, left_side=True, lower=True, transpose_a=True,
        conjugate_a=jnp.issubdtype(L.dtype, jnp.complexfloating),
        unit_diagonal=False
    )


# --------------------------------------------------------------------------- #
# VMEM-derived call ceilings
# --------------------------------------------------------------------------- #

# XLA's TPU LU custom call stages its operand through scoped VMEM: on a v5e
# a single (8192, 1024) f32 call (32 MiB) compiles, (16384, 1024) (64 MiB)
# does not — an ELEMENT-COUNT wall, so the safe height scales as
# budget / (itemsize * v). The measured v5e values (8192 rows single-call,
# 4096 batched, at v=1024 f32) are pinned by tests; other generations get
# the same model with their own budget via the device-kind table or the
# explicit override. Overridable because no public API queries scoped VMEM.
_SCOPED_VMEM_BYTES = None  # explicit override (set_scoped_vmem_bytes)

# budget per TPU device kind, bytes. Only v5e is measured; a TPU kind
# missing here is an error (scoped_vmem_bytes), never a silent guess.
_SCOPED_VMEM_TABLE = {
    "v5 lite": 32 << 20,
    "v5e": 32 << 20,
}
# the budget of the MODEL off-TPU (CPU tests, the NumPy spec): the v5e
# figure, so CPU runs size chunks exactly as the measured chip does
_MODEL_VMEM_BYTES = 32 << 20


def set_scoped_vmem_bytes(n: int | None) -> None:
    """Override the scoped-VMEM budget the chunk ceilings derive from
    (None restores device-kind detection). Use when a new TPU generation
    mis-sizes: the pinned table only knows measured hardware."""
    global _SCOPED_VMEM_BYTES
    if n is not None and n < (1 << 20):
        raise ValueError(f"implausible scoped VMEM budget {n} bytes")
    _SCOPED_VMEM_BYTES = n


def scoped_vmem_bytes() -> int:
    """The scoped-VMEM budget bounding a single LU/QR custom call's
    operand: override > $CONFLUX_TPU_SCOPED_VMEM_BYTES > TPU device-kind
    table (an unknown TPU kind raises) > the model budget off-TPU.
    Device detection may initialize a backend; pure-host callers (e.g. the NumPy spec) pass an explicit
    `budget` to the ceiling helpers instead of reaching this."""
    if _SCOPED_VMEM_BYTES is not None:
        return _SCOPED_VMEM_BYTES
    import os

    env = os.environ.get("CONFLUX_TPU_SCOPED_VMEM_BYTES")
    if env:
        # same validation as set_scoped_vmem_bytes: a typo'd override on
        # the unmeasured generation the env var exists for must fail
        # loudly, not silently fall back to detection
        try:
            n = int(env)
        except ValueError:
            raise ValueError(
                f"CONFLUX_TPU_SCOPED_VMEM_BYTES={env!r} is not an "
                "integer byte count") from None
        if n < (1 << 20):
            raise ValueError(
                f"CONFLUX_TPU_SCOPED_VMEM_BYTES={env}: implausible "
                "scoped VMEM budget (< 1 MiB)")
        return n
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _MODEL_VMEM_BYTES
    kind = dev.device_kind.lower()
    for key, budget in _SCOPED_VMEM_TABLE.items():
        if key in kind:
            return budget
    raise ValueError(
        f"no scoped-VMEM budget for TPU device kind {dev.device_kind!r}: "
        "measure it and add it to _SCOPED_VMEM_TABLE, or pass one through "
        "set_scoped_vmem_bytes / $CONFLUX_TPU_SCOPED_VMEM_BYTES")


def single_call_rows(v: int, dtype=jnp.float32, budget: int | None = None
                     ) -> int:
    """Max rows of ONE (m, v) LU/QR custom call that stays within the
    scoped-VMEM budget — tile-rounded, at least one tile. v5e pin:
    single_call_rows(1024) == 8192 (the measured default nomination
    chunk). `budget` bypasses device detection (pure-host callers)."""
    budget = scoped_vmem_bytes() if budget is None else budget
    elems = budget // jnp.dtype(dtype).itemsize
    return max(v, (elems // v) // v * v)


def batched_call_rows(v: int, dtype=jnp.float32, budget: int | None = None
                      ) -> int:
    """Max per-element rows of a BATCHED (b, m, v) custom call: the batch
    shares the scoped budget, so half the single-call height.

    v5e pin: batched_call_rows(1024) == 4096 — the measured-FASTEST chunk
    as well as the safe one. The model treats that optimum as an
    ELEMENT count (4 Mi elements), so other widths get equal-footprint
    (not equal-row) defaults — e.g. 16384 rows at v=256. Only v=1024 is
    hardware-measured; per-call `chunk=` arguments override everywhere
    if a width-specific tune disagrees."""
    budget = scoped_vmem_bytes() if budget is None else budget
    elems = budget // jnp.dtype(dtype).itemsize // 2
    return max(v, (elems // v) // v * v)


# --------------------------------------------------------------------------- #
# Panel factorizations
# --------------------------------------------------------------------------- #

# 'auto' uses plain partial pivoting for short panels and the tournament for
# tall ones; 'partial'/'tournament' force one path (tests and experiments).
_PANEL_ALGO = "auto"


def set_panel_algo(name: str) -> None:
    if name not in ("auto", "partial", "tournament", "pallas"):
        raise ValueError(f"unknown panel algo {name!r}")
    global _PANEL_ALGO
    _PANEL_ALGO = name


# VMEM ceiling of the Pallas elimination kernel: the (m, 128) block, the
# int8 mask, the (m, 128) temporaries and the outputs must stay under the
# 16 MiB scoped VMEM. A described-v5e compile fits m=2048 and refuses
# m=4096 (16.88 MiB). A module var (not derived per-call) so tests can
# shrink the ceiling.
_PALLAS_MAX_ROWS = 2048


def _pallas_panel_ok(dtype, m: int, v: int) -> bool:
    """Whether the Pallas elimination kernel can factor an (m, v) panel
    (off-TPU it runs in interpret mode, so no backend check here)."""
    return (jnp.dtype(dtype) == jnp.float32 and v % 128 == 0
            and m <= _PALLAS_MAX_ROWS)


def get_panel_algo() -> str:
    return _PANEL_ALGO


def panel_lu(panel: jax.Array, algo: str | None = None):
    """Pivoted LU of an (m, v) panel.

    Returns (lu_packed, perm) where perm is a length-m row permutation such
    that panel[perm] == L @ U with L unit-lower (m, v) and U upper (v, v)
    packed into lu_packed. This is the local kernel inside tournament
    pivoting (role of `LUP`, reference `conflux_opt.hpp:143-166`).

    Short panels use exact partial pivoting (`lax.linalg.lu`); tall panels
    use communication-avoiding tournament pivoting (:func:`panel_lu_tournament`),
    which bounds every LU call's height and keeps the MXU busy.

    `algo` defaults to the module setting **at trace time**; jitted callers
    must resolve it outside jit and pass it as a static argument (see
    `conflux_tpu/lu/single.py`) so it participates in the jit cache key.

    Tile-size ceiling on TPU: every LU call is at least v rows tall (the
    tournament's reduction rounds stack 2v), and XLA's LuDecompositionBlock
    custom call overflows its scoped VMEM at ~16384 rows — so v <= 4096 is
    the safe regime on TPU (v=1024 measured fastest anyway; the
    benchmark's LU cells run it, `benchmark/configs/`).
    """
    m, v = panel.shape
    algo = _resolve_panel_algo(
        panel.dtype, m, v, _PANEL_ALGO if algo is None else algo
    )
    if algo == "pallas":
        if m > _PALLAS_MAX_ROWS:  # too tall for VMEM: tournament over chunks
            return panel_lu_tournament(panel, chunk=_PALLAS_MAX_ROWS,
                                       use_pallas=True)
        return panel_lu_pallas(panel)
    if algo == "tournament":
        return panel_lu_tournament(panel)
    lu_packed, _pivots, perm = lax.linalg.lu(panel)
    return lu_packed, perm


def _resolve_panel_algo(dtype, m: int, v: int, algo: str) -> str:
    """Shared dispatch for :func:`panel_lu` / :func:`panel_winners`:
    validate, resolve 'auto', and gate the pallas kernel's eligibility."""
    if algo not in ("auto", "partial", "tournament", "pallas"):
        raise ValueError(f"unknown panel algo {algo!r}")
    if algo == "auto":
        # measured on v5e (m=4096, v=1024): XLA custom call 11.7 ms, pallas
        # masked elimination 17 ms (its per-step scalar reductions serialize
        # the pipeline) — so 'auto' prefers partial/tournament and 'pallas'
        # stays opt-in until the kernel wins. The threshold derives from
        # the COMPUTE dtype: a bf16 panel runs f32 panel math, so its
        # single exact-LU call is f32-sized
        cd = compute_dtype(dtype)
        algo = ("tournament" if m > 2 * max(batched_call_rows(v, cd), v)
                else "partial")
    if algo == "pallas" and not _pallas_panel_ok(dtype, min(m, _PALLAS_MAX_ROWS), v):
        raise ValueError(
            f"pallas panel kernel supports float32 with width a multiple "
            f"of 128, got {jnp.dtype(dtype)} ({m}, {v})"
        )
    return algo


def chunk_layout(m: int, v: int, chunk: int | None = None) -> tuple[int, int]:
    """(chunk height c, chunk count nch) used by :func:`tournament_winners`
    for an (m, v) panel — exposed so callers can build per-chunk liveness
    predicates with the same rounding. The default chunk is the batched
    VMEM-safe height for width v (the chunk round is a batched call)."""
    c = chunk if chunk is not None else batched_call_rows(v)
    c = min(c, -(-m // v) * v)  # never taller than the (tile-rounded) panel
    c = max(v, c // v * v)  # multiple of v, at least one tile tall
    return c, -(-m // c)


def tournament_winners(panel: jax.Array, chunk: int | None = None,
                       use_pallas: bool = False, chunk_live=None,
                       tree: str = "pairwise"):
    """Elect v pivot rows of an (m, v) panel by tournament (CALU).

    Single-device analogue of the reference's butterfly tournament
    (`tournament_rounds`, `conflux_opt.hpp:220-336`): rows are split into
    chunks, each chunk's local partial-pivoted LU nominates its top v rows,
    and a binary reduction tree of stacked (2v, v) LUs elects the winners.
    All LU calls are height-bounded (chunk or 2v rows) and the chunk round
    is batched, so this scales to arbitrarily tall panels.

    `tree` picks the reduction shape after nomination: 'pairwise' is the
    binary tree above (log2(nch) batched rounds); 'flat' stacks ALL
    nominees into one (nch*v, v) LU call — fewer sequential custom calls
    (each is latency-bound in its serial column sweep, so call count is
    the cost driver on TPU), at the price of a taller single call. 'flat'
    requires nch*v within the single-call VMEM-safe height (~8192 rows at
    v=1024 measured; the caller picks tree='flat' only when that holds).
    Both trees elect with identical tie-breaking semantics (zero pad rows
    lose every contest) but may order DIFFERENT winners for rank-deficient
    or tied inputs; at full rank the winner SET matches partial pivoting's
    growth properties either way (CALU's guarantee, not bitwise equality
    between trees).

    `chunk_live`, if given, is a (nch,)-shaped traced bool vector (see
    :func:`chunk_layout`): chunk i's LU is skipped via `lax.cond` when
    chunk_live[i] is False, nominating zero rows instead (which lose every
    contest). Callers whose dead rows form a prefix (the distributed LU's
    LAPACK-order layout) use this to shrink the election with the active
    window. With chunk_live, lu00 is only meaningful if the winners went
    through a live path (guaranteed when any live row exists and nch == 1,
    or via the reduction tree when nch > 1).

    Returns (lu00, gpiv): lu00 is the packed (v, v) LU of the winning rows in
    pivot order; gpiv gives their row indices in `panel`. Requires the panel
    to have full column rank: a rank-deficient panel can elect zero pad rows,
    whose out-of-range ids are dropped by the caller's scatter (the same
    panels break exact partial pivoting too — zero pivots).
    """
    m, v = panel.shape
    if m < v:
        raise ValueError(
            f"tournament_winners needs m >= v, got ({m}, {v}): a shorter "
            "panel would elect zero-pad rows with out-of-range ids even at "
            "full rank"
        )
    if tree not in ("pairwise", "flat"):
        raise ValueError(f"unknown tree {tree!r} (pairwise|flat)")
    c, nch = chunk_layout(m, v, chunk)
    mp = nch * c
    if mp != m:  # zero rows lose every pivot contest against real rows
        panel = jnp.pad(panel, ((0, mp - m), (0, 0)))
    ids = jnp.arange(mp, dtype=jnp.int32)

    cand = panel.reshape(nch, c, v)
    cid = ids.reshape(nch, c)
    if use_pallas and _pallas_panel_ok(panel.dtype, c, v):
        outs = [panel_lu_pallas(cand[i]) for i in range(nch)]
        perm_c = jnp.stack([o[1] for o in outs])
        lu0 = outs[0][0][:v]
    elif chunk_live is not None:

        def chunk_lu(ci):
            lu_i, _, perm_i = lax.linalg.lu(ci)
            return lu_i, perm_i

        def chunk_dead(ci):
            # zero nominees (lose every contest); identity order
            perm_i = jnp.arange(c, dtype=jnp.int32) + jnp.zeros_like(
                ci[:, 0], jnp.int32)
            return jnp.zeros_like(ci), perm_i

        outs = [lax.cond(chunk_live[i], chunk_lu, chunk_dead, cand[i])
                for i in range(nch)]
        perm_c = jnp.stack([o[1] for o in outs])
        lu0 = outs[0][0][:v]
    else:
        lu_c, _, perm_c = lax.linalg.lu(cand)  # batched (nch, c, v)
        lu0 = lu_c[0, :v]
    top = perm_c[:, :v]
    win = jnp.take_along_axis(cand, top[:, :, None], axis=1)  # (nch, v, v)
    wid = jnp.take_along_axis(cid, top, axis=1)

    if nch == 1:  # single chunk: its local LU already decided everything
        return lu0, wid[0]

    if tree == "flat":
        # one (nch*v, v) LU elects straight from all nominees: 1 sequential
        # custom call instead of log2(nch) tree rounds
        stack = win.reshape(nch * v, v)
        sid = wid.reshape(nch * v)
        lu_f, _, perm_f = lax.linalg.lu(stack)
        top = perm_f[:v]
        return lu_f[:v], jnp.take(sid, top, mode="fill", fill_value=mp)

    n = 1 << (nch - 1).bit_length()
    if n != nch:
        # pad blocks are all-zero rows with out-of-range ids: they lose every
        # contest against full-rank data, and if ever elected (rank-deficient
        # panel) their ids are dropped by the caller's scatter, not aliased
        # onto a real row
        win = jnp.pad(win, ((0, n - nch), (0, 0), (0, 0)))
        wid = jnp.pad(wid, ((0, n - nch), (0, 0)), constant_values=mp)

    lu_top = None
    while n > 1:
        stacked = win.reshape(n // 2, 2 * v, v)
        sid = wid.reshape(n // 2, 2 * v)
        if use_pallas and _pallas_panel_ok(panel.dtype, 2 * v, v):
            outs = [panel_lu_pallas(stacked[i]) for i in range(n // 2)]
            perm_r = jnp.stack([o[1] for o in outs])
            lu_top = jnp.stack([o[0][:v] for o in outs])
        else:
            lu_r, _, perm_r = lax.linalg.lu(stacked)  # batched (n/2, 2v, v)
            lu_top = lu_r[:, :v]
        top = perm_r[:, :v]
        win = jnp.take_along_axis(stacked, top[:, :, None], axis=1)
        wid = jnp.take_along_axis(sid, top, axis=1)
        n //= 2
    # final round's packed LU rows 0..v are exactly the winners, factored
    return lu_top[0], wid[0]


def panel_lu_pallas(panel: jax.Array):
    """Blocked panel LU with full-height partial pivoting, Pallas elimination.

    Same contract as :func:`panel_lu`. The (m, v) panel is factored in
    128-wide column blocks; each block is eliminated by the VMEM-resident
    Pallas kernel (`pallas_kernels.lu_block`) with *no row movement* — pivot
    rows keep their positions, an `alive` mask shrinks, and the inter-block
    update is a row-gathered TRSM plus one masked MXU GEMM. Rows are gathered
    into LAPACK order exactly once at the end. This sidesteps the serial
    row-swapping LU custom call entirely (VMEM bound: m <= 2048, see
    `pallas_kernels.lu_block`; taller panels go through
    `panel_lu(algo='pallas')`, which routes them to the tournament with
    pallas chunks).
    """
    from conflux_tpu.ops import pallas_kernels

    w = pallas_kernels._PANEL_W
    m, v = panel.shape
    if v % w:
        raise ValueError(f"panel width {v} not a multiple of {w}")
    A = panel
    alive = jnp.ones((m, 1), jnp.int8)
    pivs = []
    for off in range(0, v, w):
        blk = lax.dynamic_slice(A, (0, off), (m, w))
        out, alive_new, piv = pallas_kernels.lu_block(blk, alive)
        A = lax.dynamic_update_slice(A, out, (0, off))
        pivrows = piv[0]  # (w,) absolute row ids in pivot order
        pivs.append(pivrows)
        if off + w < v:
            # inter-block update on the trailing columns of the panel
            L00 = out[pivrows]  # (w, w) packed rows in pivot order
            rest = lax.dynamic_slice(A, (0, off + w), (m, v - off - w))
            U01 = trsm_left_lower_unit(unit_lower(L00), rest[pivrows])
            # multipliers of still-live rows only (pivot rows contribute 0)
            L10 = jnp.where(alive_new != 0, out, 0.0)
            rest = rest - jnp.matmul(
                L10, U01, precision=lax.Precision.HIGHEST,
                preferred_element_type=_acc_dtype(L10.dtype),
            ).astype(rest.dtype)
            rest = rest.at[pivrows].set(U01)
            A = lax.dynamic_update_slice(A, rest, (0, off + w))
        alive = alive_new
    gpiv = jnp.concatenate(pivs)  # (v,) rows in elimination order
    ids = jnp.arange(m, dtype=jnp.int32)
    is_piv = jnp.zeros((m,), bool).at[gpiv].set(True, mode="drop")
    pos = jnp.zeros((m,), jnp.int32).at[gpiv].set(
        jnp.arange(v, dtype=jnp.int32), mode="drop"
    )
    key = jnp.where(is_piv, pos, v + ids)
    perm = jnp.argsort(key)
    return A[perm], perm


def panel_winners(panel: jax.Array, algo: str = "auto"):
    """Elect the v pivot rows of an (m, v) panel and factor them.

    Returns (lu00, gpiv): the packed (v, v) LU of the winners in pivot order
    and their row positions in `panel`. This is the selection half of
    :func:`panel_lu` without the L10 solve or row reordering — callers that
    place rows themselves (see `conflux_tpu/lu/single.py`'s swap-minimal
    update) use this directly. For rank-deficient panels the tournament may
    report out-of-range pad ids in gpiv (see :func:`tournament_winners`);
    `permute.swap_minimal_perm` sanitizes them.
    """
    m, v = panel.shape
    algo = _resolve_panel_algo(panel.dtype, m, v, algo)
    if algo == "pallas":
        if m <= _PALLAS_MAX_ROWS:
            lu_packed, perm = panel_lu_pallas(panel)
            return lu_packed[:v], perm[:v]
        return tournament_winners(panel, chunk=_PALLAS_MAX_ROWS, use_pallas=True)
    if algo == "partial":
        lu_f, _, perm = lax.linalg.lu(panel)
        return lu_f[:v], perm[:v]
    return tournament_winners(panel)


def panel_lu_tournament(panel: jax.Array, chunk: int | None = None,
                        use_pallas: bool = False):
    """Tournament-pivoted (CALU) LU of a tall (m, v) panel.

    Same contract as :func:`panel_lu`. Pivot growth of CALU is bounded and
    in practice indistinguishable from partial pivoting (the reference ships
    the same trade, `python/pivoting.py` 'tournament' strategy); residuals are
    checked by the test suite, not assumed. `use_pallas` runs the chunk and
    reduction-tree factorizations through the Pallas elimination kernel
    instead of the XLA custom call.
    """
    m, v = panel.shape
    lu00, gpiv = tournament_winners(panel, chunk, use_pallas)
    ids = jnp.arange(m, dtype=jnp.int32)
    is_piv = jnp.zeros((m,), bool).at[gpiv].set(True, mode="drop")
    pos = jnp.zeros((m,), jnp.int32).at[gpiv].set(
        jnp.arange(v, dtype=jnp.int32), mode="drop"
    )
    # winners first (in pivot order), remaining rows after (in original order)
    key = jnp.where(is_piv, pos, v + ids)
    perm = jnp.argsort(key)
    rest = panel[perm[v:]]
    L10 = trsm_right_upper(jnp.triu(lu00), rest)
    return jnp.concatenate([lu00, L10], axis=0), perm


def unit_lower(lu00: jax.Array) -> jax.Array:
    """Extract the unit-lower L00 from a packed (v, v) LU diagonal block."""
    v = lu00.shape[0]
    return jnp.tril(lu00, -1) + jnp.eye(v, dtype=lu00.dtype)


def potrf(a: jax.Array) -> jax.Array:
    """Lower Cholesky factor of a v x v SPD tile (reference dpotrf,
    `Cholesky.cpp:188-194`)."""
    return lax.linalg.cholesky(a, symmetrize_input=False)
