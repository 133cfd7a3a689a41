"""Device self time per factorization of the LU's row swaps (step 2) as
the program's own attribution (`conflux_tpu.profiler.scope_map`) credits
them: the `step2_pivotrows` ops and the per-row scatter loop XLA generates
for them, from the trace."""

from benchmark import attribution


def read(obs):
    t = attribution.scope_self_s(obs)
    if t is None or not t.get("step2_pivotrows"):
        return None
    return 1e3 * t["step2_pivotrows"]
