"""Device self time per factorization of the tournament election: the ops
under the LU's `step0_reduce` and `step1_pivoting` scopes."""


def read(obs):
    tr, steps = obs.get("trace"), obs.get("steps")
    if tr is None or not steps or not tr.owned_ops("factor"):
        return None
    t = tr.scope_self_s("factor", r"step0_reduce|step1_pivoting")
    return 1e3 * t / steps if t > 0 else None
