"""Device self time per factorization and chip of the tournament election
on the mesh: `lu.election_ms` (the ops under `step0_reduce` and
`step1_pivoting`, their collectives included, summed over the chips)
divided by the number of chips."""

from benchmark import run


def read(obs):
    t = run.read_metric("lu.election_ms", obs)
    return None if t is None else t / obs["trace"].chips
