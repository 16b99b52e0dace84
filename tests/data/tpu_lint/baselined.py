"""Fixture: a real finding that tests grandfather through a baseline
file (written by the test, not committed)."""
import jax


def old_code(x):
    return jax.tree_map  # known finding, baselined in the test
