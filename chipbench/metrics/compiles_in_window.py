"""Programs JAX compiled inside the measured window (``jax.monitoring``
backend-compile events).  Set-up warms every shape the window uses, so a
sound run reads 0."""


def read(win):
    return float(win.compiles)
