"""Entry / compile-cache layer: programs compiled (or fetched from the
compilation cache) inside the measured window, counted by the harness's
listener on jax's backend-compile event. Expected 0."""


def reduce(run: dict):
    return run["compiles_in_window"]
