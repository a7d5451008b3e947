"""Set-up seconds JAX spent compiling, persistent-cache reads included
(``jax.monitoring`` backend-compile events, summed over the set-up)."""


def read(rec):
    return rec["compile_s"]
