"""`jax.monitoring` backend-compile events between the window's first and
last stamp; expected 0."""
NAME, UNIT = "compiles_in_window", "count"


def applies(cell):
    return True


def read(ctx):
    return ctx["compiles_in_window"]
