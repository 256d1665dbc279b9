"""Public wrapper for embedding-bag with fallback to the jnp oracle."""
from __future__ import annotations

import jax

from repro.kernels.common import default_interpret
from repro.kernels.embedding_bag.kernel import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref


def embedding_bag_op(
    table: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    *,
    bb: int = 256,
    bv: int = 8192,
    use_kernel: bool | None = None,
) -> jax.Array:
    if use_kernel is None:
        use_kernel = idx.shape[0] >= 128
    if not use_kernel:
        return embedding_bag_ref(table, idx, w)
    return embedding_bag(table, idx, w, bb=bb, bv=bv, interpret=default_interpret())
