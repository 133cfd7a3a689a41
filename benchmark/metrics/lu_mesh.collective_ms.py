"""Device self time per factorization and chip of the factor's
collectives: the ops whose own scope names a `comm_*` scope (the panel
psum, the election's gather or exchanges, the winner-row, displaced-row
and diagonal-panel psums, the epilogue's), summed over the chips and
divided by their number. A program without such scopes reads None."""


def read(obs):
    tr, steps = obs.get("trace"), obs.get("steps")
    if tr is None or not steps or not tr.owned_ops("factor"):
        return None
    t = tr.scope_self_s("factor", r"comm_[a-z0-9_]+")
    return 1e3 * t / steps / tr.chips if t > 0 else None
