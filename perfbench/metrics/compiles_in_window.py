"""XLA compilations, persistent-cache loads included, that started inside
the measured window (JAX's ``backend_compile`` monitoring events)."""


def read(run):
    return float(run.compiles_in_window)
