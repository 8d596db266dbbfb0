"""Device milliseconds per steady step of the dispatched optimizer ops that
ran the jnp path (the program's ``<op>.jnp`` stages): on a TPU, the shapes
the Pallas kernels refuse."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, r"[a-z_]+\.jnp")
