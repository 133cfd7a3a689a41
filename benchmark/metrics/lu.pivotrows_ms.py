"""Device self time per factorization of the ops under the LU's
`step2_pivotrows` scope (the row-swap scatter), from the trace."""


def read(obs):
    tr, steps = obs.get("trace"), obs.get("steps")
    if tr is None or not steps or not tr.owned_ops("factor"):
        return None
    t = tr.scope_self_s("factor", r"step2_pivotrows")
    return 1e3 * t / steps if t > 0 else None
