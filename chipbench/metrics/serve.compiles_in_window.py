"""Backend compiles (persistent-cache reads included) that JAX reported
between the opening and the closing of the window: 0 when set-up warmed
every shape the traffic uses."""


def read(outcome, run):
    return float(outcome.observed["compiles_in_window"])
