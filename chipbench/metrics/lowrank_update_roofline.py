"""Projected-momentum update kernel: the least time its work needs at the
called shapes over the device time of its events, per steady step."""
from chipbench import kernels


def read(run):
    return kernels.roofline(run, "lowrank_update")
