"""Share of the roofline reached by the trailing update (`step6_dgemm`):
the least time at the published bf16 peak and HBM bandwidth for the
update's flops and bytes (counted from shapes, `flops.trailing_update`)
over the scope's device self time. An f32 HIGHEST matmul runs as six
bf16 passes on the MXU, so this share cannot pass about 1/6."""

from benchmark import flops


def read(obs):
    tr, steps = obs.get("trace"), obs.get("steps")
    if tr is None or not steps or not tr.owned_ops("factor"):
        return None
    t = tr.scope_self_s("factor", r"step6_dgemm")
    if t <= 0:
        return None
    f, b = obs["trailing"]
    return flops.roofline_share(f * steps, b * steps, t, obs["device_kind"])
