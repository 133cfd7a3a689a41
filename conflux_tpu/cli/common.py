"""Shared CLI plumbing for the miniapps."""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax


def add_common_args(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--platform", default=None, choices=["cpu", "tpu"],
        help="force a JAX platform (default: whatever the environment gives); "
        "'cpu' also enables --devices simulated host devices; 'tpu' fails "
        "when JAX finds no TPU",
    )
    p.add_argument(
        "--devices", type=int, default=8,
        help="simulated device count when --platform cpu (default 8)",
    )
    p.add_argument(
        "--dtype", default="float32", choices=["float32", "float64", "bfloat16"],
        help="element type (float64 requires a CPU platform: the TPU LU "
        "custom call is f32-only)",
    )
    p.add_argument("--profile", action="store_true", help="print region timings")


def positive_int(text: str) -> int:
    """argparse type for counts that must be >= 1."""
    try:
        n = int(text)
    except ValueError:
        n = 0
    if n < 1:
        raise argparse.ArgumentTypeError(
            f"expected a positive integer, got {text!r}")
    return n


def segs_arg(text: str) -> tuple[int, int]:
    """argparse type for --segs RxC (e.g. '16x16'): two positive ints."""
    r, sep, c = text.lower().partition("x")
    try:
        segs = (int(r), int(c))
    except ValueError:
        segs = None
    if not sep or segs is None or segs[0] < 1 or segs[1] < 1:
        raise argparse.ArgumentTypeError(
            f"expected RxC with positive integers (e.g. 16x16), got {text!r}")
    return segs


def setup_platform(args) -> None:
    """Must run before any JAX backend initializes."""
    from conflux_tpu import cache

    # persistent XLA compile cache (conflux_tpu.cache): at-scale programs
    # cost minutes of compile; every CLI process shares the warmed cache
    cache.enable_persistent_cache()
    if args.platform == "cpu":
        import os

        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )
        jax.config.update("jax_platforms", "cpu")
    elif args.platform == "tpu":
        require_tpu()
    if args.dtype == "float64" or getattr(args, "refine", None) is not None:
        # --refine computes its residuals in f64 (O(N^2) work only;
        # software-emulated on TPU) — the HPL-MxP recipe's high half
        jax.config.update("jax_enable_x64", True)


def require_tpu() -> None:
    """Exit non-zero unless JAX's default device is a TPU — never let a
    TPU run quietly become a CPU one."""
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        raise SystemExit(f"--platform tpu: JAX found no TPU ({e})") from None
    if dev.platform != "tpu":
        raise SystemExit(
            f"--platform tpu: JAX's default device is {dev.platform!r} "
            f"({dev.device_kind}), not a TPU")


def np_dtype(name: str):
    return {"float32": np.float32, "float64": np.float64, "bfloat16": np.float32}[name]


def sync(x):
    """Block until x (an array or pytree of arrays) is computed."""
    return jax.block_until_ready(x)


def refine_report(solve_fn, A_host, out_dtype, sweeps: int) -> float:
    """Shared --refine epilogue of the miniapps: solve A x = 1 with
    `sweeps` classic-IR rounds (f64 residuals — the HPL-MxP recipe),
    print the `_solve_residual_` line, return the relative residual.
    The residual is measured against the matrix actually factored, in
    its own dtype; corrections ride the factors' compute dtype."""
    import jax.numpy as jnp

    from conflux_tpu import solvers
    from conflux_tpu.ops import blas

    n = A_host.shape[0]
    b = jnp.ones((n,), A_host.dtype)
    Adev = jnp.asarray(A_host)
    corr_dtype = blas.compute_dtype(jnp.dtype(out_dtype))
    x = solvers.refine_classic(solve_fn, Adev, b, sweeps, jnp.float64,
                               corr_dtype)
    r = solvers._residual_strips(Adev, x, b.astype(jnp.float64),
                                 jnp.float64)
    rel = float(jnp.linalg.norm(r) / jnp.linalg.norm(b.astype(jnp.float64)))
    flag = "PASS" if rel <= 1e-6 else "----"
    print(f"_solve_residual_ refine={sweeps} rel={rel:.3e} [{flag} <=1e-6]")
    return rel


class WallTimer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = (time.perf_counter() - self.t0) * 1e3


def phase_profile(program, dev) -> None:
    """Per-phase device-time table for a distributed hot loop (the
    reference's per-step semiprof table, `README.md:120-165`): one extra
    run under `jax.profiler.trace`, attributed by the compiled program's
    own scopes (`profiler.phase_table`)."""
    import tempfile

    from conflux_tpu import profiler

    comp = program.lower(dev).compile()
    with tempfile.TemporaryDirectory(prefix="conflux-phases-") as trace_dir:
        with profiler.trace(trace_dir):
            with profiler.region("profile.launch"):
                out = comp(dev)
            with profiler.region("profile.wait"):
                sync(out[0] if isinstance(out, tuple) else out)
        try:
            profiler.phase_table(trace_dir, comp.as_text())
        except (FileNotFoundError, ValueError) as e:
            # a CPU run has no device plane to read; the host-region
            # report still prints
            print(f"(no device phase table: {e})")


def add_experiment_type_arg(p) -> None:
    """The reference's -t vocabulary (`examples/conflux_miniapp.cpp:63-66`)."""
    p.add_argument(
        "-t", "--type", default="weak", choices=["weak", "strong"],
        help="experiment type: sets the reported N_base (reference "
        "convention: N / int(sqrt(P)) for weak scaling, N for strong)",
    )


def result_line(algo: str, N: int, P: int, grid, exp_type: str,
                ms: float, v: int, dtype: str) -> str:
    """Reference line shape (`examples/conflux_miniapp.cpp:136-165`):
    `_result_ <algo>,<impl>,<N>,<N_base>,<P>,<grid>,time,<weak|strong>,<ms>,<v>`
    with N_base = N // int(sqrt(P)) under weak scaling (the reference
    truncates the sqrt — NOT a rounded float division), and the dtype
    appended as an 11th field fixed-width parsers ignore."""
    import math

    n_base = N // math.isqrt(P) if exp_type == "weak" else N
    return (f"_result_ {algo},conflux_tpu,{N},{n_base},{P},"
            f"{grid},time,{exp_type},{ms:.3f},{v},{dtype}")


def add_auto_arg(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--auto", action="store_true",
        help="resolve tuning knobs you did not pass from the measured "
        "dispatch table (conflux_tpu.autotune — the role of the "
        "reference's hand-measured variant switch, Cholesky.cpp:857-921); "
        "prints the applied knobs and the measurement they came from. Any "
        "explicitly passed flag pins its knob, even at the library "
        "default value",
    )


def apply_auto(args, algo: str, N: int, P: int, dtype: str,
               flag_knobs: dict) -> None:
    """--auto resolution: for every (args attribute -> (knob name, library
    default)) in `flag_knobs`, an un-passed flag is replaced by the
    measured recommendation's knob (None knobs never overwrite).

    Auto-eligible flags are declared with a `default=None` SENTINEL, so
    "un-passed" is detected as `is None` — an explicitly passed flag
    always pins its knob, even when the passed value equals the library
    default (ADVICE r4 #1: `--auto --election gather` must run gather).
    Callers must follow apply_auto with resolve_knob_defaults(), which
    fills any attribute still None with its library default.

    With an empty `flag_knobs` (a mode with nothing auto-tunable) the
    dispatch table is not consulted and a distinct line says so
    (ADVICE r4 #4 — "(all knobs pinned)" would misreport).

    Prints `_auto_` lines (knobs + provenance) in the miniapp protocol
    style: one space-free key=value token per knob (tuples in the RxC
    grammar), so whitespace-splitting sweep parsers stay correct."""
    if not flag_knobs:
        print("_auto_ (no auto-tunable knobs for this mode)")
        return
    from conflux_tpu import autotune

    rec = autotune.recommended(algo, N, P=P, dtype=str(dtype))

    def fmt(v):
        # one token vocabulary for sweep parsers: tuples in the RxC
        # grammar, bools as on/off (the tune-log grammar and
        # apply_flip_criteria vocabulary — a Python bool repr here would
        # hand parsers a second spelling of the same knob state; bool
        # check first, bool is an int subclass)
        if isinstance(v, bool):
            return "on" if v else "off"
        return "x".join(map(str, v)) if isinstance(v, tuple) else v

    applied = []
    for attr, (knob, _default) in flag_knobs.items():
        if getattr(args, attr) is None and rec.knobs.get(knob) is not None:
            setattr(args, attr, rec.knobs[knob])
            applied.append(f"{attr}={fmt(rec.knobs[knob])}")
    print(f"_auto_ {' '.join(applied) if applied else '(all knobs pinned)'}")
    print(f"_auto_provenance_ {rec.provenance}")


def resolve_knob_defaults(args, flag_knobs: dict) -> None:
    """Fill every auto-eligible attribute still at its None sentinel with
    its library default — run after apply_auto (or instead of it when
    --auto is off). Kept separate so apply_auto can tell "un-passed"
    from "explicitly passed at the default value"."""
    for attr, (_knob, default) in flag_knobs.items():
        if getattr(args, attr) is None:
            setattr(args, attr, default)
