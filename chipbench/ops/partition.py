"""A repartition with no combiner (one side of a co-partitioned join):
every input row, unchanged.  numpy alone."""
import numpy as np


def expected(keys, vals):
    """Every row, sorted by key and by value within a key."""
    order = np.lexsort(tuple(vals.T[::-1]) + (keys,))
    return keys[order], vals[order]


def lowered(keys, vals):
    """Every row with its values carried in float32 on the default JAX
    device."""
    import jax.numpy as jnp

    return keys, np.asarray(jnp.asarray(vals, jnp.float32), np.float64)
