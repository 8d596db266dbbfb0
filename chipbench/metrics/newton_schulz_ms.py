"""Device milliseconds per steady step of Newton–Schulz, the Pallas kernels
and the jnp path alike (the program's ``newton_schulz.<impl>`` stages)."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "newton_schulz")
