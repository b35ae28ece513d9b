"""Group-by with a sum (TPC-H Q18's and Q17's aggregations): one row per
distinct key, each value column summed.  numpy alone."""
import numpy as np


def expected(keys, vals):
    """The distinct keys, sorted, and each one's column sums."""
    uniq, inv = np.unique(keys, return_inverse=True)
    sums = np.stack([np.bincount(inv, weights=vals[:, c], minlength=uniq.size)
                     for c in range(vals.shape[1])], axis=1)
    return uniq, sums


def lowered(keys, vals):
    """The same sums taken in float32 on the default JAX device."""
    import jax
    import jax.numpy as jnp

    uniq, inv = np.unique(keys, return_inverse=True)
    out = jax.ops.segment_sum(jnp.asarray(vals, jnp.float32),
                              jnp.asarray(inv, jnp.int32), uniq.size)
    return uniq, np.asarray(out, np.float64)
