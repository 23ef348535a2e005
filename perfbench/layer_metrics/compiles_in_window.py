"""Programs JAX compiled between the window's start and end (its own
`Compiling <name>` log records).  Must be 0, or the run is not `correct`."""


def read(obs):
    return float(len(obs["compiled_in_window"]))
