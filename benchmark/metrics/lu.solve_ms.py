"""Device self time per step of the solve, the executions of the
`jit_lu_solve` program on the trace's `XLA Modules` line."""

from benchmark import attribution


def read(obs):
    t = attribution.module_self_s(obs, "jit_lu_solve")
    return None if t is None else 1e3 * t
