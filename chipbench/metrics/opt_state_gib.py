"""Bytes of the optimizer-state pytree on the fullest chip, in GiB."""


def read(run):
    return run.got["opt_state_bytes"] / 1024 ** 3
