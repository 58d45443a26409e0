"""XLA backend compiles between the opening and the closing checkpoint
(jax monitoring events). A warm run has none."""


def read(record):
    return float(record["compiles_in_window"])
