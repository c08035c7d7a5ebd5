"""XLA executables built or loaded inside the refresh window, counted
by a ``jax.monitoring`` listener on JAX's backend-compile event.  The
write programs take bucketed operands and the warm-up pair builds them,
so this reads 0."""


def read(r):
    return r.compiles
