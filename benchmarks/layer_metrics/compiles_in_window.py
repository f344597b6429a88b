"""compile: executables jax asked its backend for between window start and end (``jax.monitoring``).
Warm-up has run every query until a pass compiled nothing, so this should read 0."""


def read(run):
    return run.compiles_in_window
