"""Low-rank projection kernels (``PᵀG`` fused with the momentum update,
and ``PᵀG`` alone): the least time their work needs at the called shapes
over the device time of their events, per steady step."""
from chipbench import kernels


def read(run):
    return kernels.roofline(run, "lowrank_update")
