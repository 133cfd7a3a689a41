"""Device self time per step and chip of the distributed solve:
`lu.solve_ms` (the `jit_lu_solve` executions, summed over the chips)
divided by the number of chips."""

from benchmark import run


def read(obs):
    t = run.read_metric("lu.solve_ms", obs)
    return None if t is None else t / obs["trace"].chips
