"""XLA executables built or loaded inside the measured window, counted
by a ``jax.monitoring`` listener on JAX's backend-compile event.
Everything the window runs is warmed up in set-up, so this reads 0."""


def read(r):
    return r.compiles
