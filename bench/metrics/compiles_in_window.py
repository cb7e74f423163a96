"""Backend compiles that started inside the window (should be 0); JAX's
event also times a load from the persistent cache, so a program first met
inside the window counts either way."""


def read(run):
    return run.compiles_in_window
