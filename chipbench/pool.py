"""The input pool: `n` distinct batches made on the device from the seed
by one jitted program, resident and cycled, so that no step repeats the
previous one's data and no upload is timed."""
from __future__ import annotations

import jax


def make_pool(model, cfg, seed, batch, n, sharding):
    """[(x, y)] * n, laid out by `sharding` (batch axis over the mesh).
    Every seed gives the same shapes, so the seed never changes the work.
    """
    make = jax.jit(lambda key: model.make_batch(cfg, key, batch),
                   out_shardings=(sharding, sharding))
    # % 2**32: a seed may exceed 32 bits; the key's second word keeps
    # neighbouring seeds apart all the same.
    root = jax.random.PRNGKey(seed % (1 << 32))
    pool = [make(jax.random.fold_in(root, i)) for i in range(n)]
    jax.block_until_ready(pool)
    return pool
