"""Share of the roofline reached by the mesh's trailing update
(`step6_dgemm`), read as `lu.dgemm_roofline` reads it: the least time at
one chip's published peaks for the whole update's flops and bytes
(`flops.trailing_update`, from the global shapes: the chips' local blocks
sum to the same flops, and to more bytes, so the bytes stay a lower
bound) over the scope's device self time summed over the chips, which is
the mean of the chips' shares. An f32 HIGHEST matmul runs as six bf16
passes, so it cannot pass about 1/6."""

from benchmark import run


def read(obs):
    return run.read_metric("lu.dgemm_roofline", obs)
