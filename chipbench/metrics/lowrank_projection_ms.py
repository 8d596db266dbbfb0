"""Device milliseconds per steady step of the low-rank projection: the
program's ``lowrank.project`` (family stacking, PᵀG and the momentum) and
``lowrank.back_project`` stages."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, r"lowrank\.project", r"lowrank\.back_project")
