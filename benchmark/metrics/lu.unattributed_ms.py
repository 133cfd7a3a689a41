"""Device self time per factorization of the factor's ops that the
program's own attribution (`conflux_tpu.profiler.scope_map`) credits to no
step scope, from the trace."""

from benchmark import attribution


def read(obs):
    t = attribution.scope_self_s(obs)
    return None if t is None else 1e3 * t.get(None, 0.0)
