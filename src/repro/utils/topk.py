"""``top_k`` with ``lax.top_k``'s tie rule at any batch width.

The simulator's policies rank pages with ``top_k`` under the lane
``vmap``, and its results must not depend on how many lanes share a
program (the mesh fabric shards lanes over devices).  On a TPU v5e,
``lax.top_k`` over rows holding tied values picked different indices at
42 rows than at 8, 168 or 256.  There a two-key sort over (value,
index) gives the rule exactly: a total order has one answer at any
width.  Elsewhere ``lax.top_k`` keeps its rule and is much faster than
a sort.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _descending_key(x):
    """An integer key whose ascending order is ``x``'s descending order
    under ``lax.top_k``'s total order (+0.0 above -0.0; NaN above +inf)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        sign = jnp.uint32(0x80000000)
        return ~jnp.where((u & sign) != 0, ~u, u | sign)
    return ~x


def top_k_sorted(x, k: int):
    """``top_k`` by a full two-key sort over (descending key, index)
    along the last axis."""
    axis = x.ndim - 1
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    _, idx = jax.lax.sort((_descending_key(x), iota), dimension=axis,
                          num_keys=2)
    idx = idx[..., :k]
    return jnp.take_along_axis(x, idx, axis=axis), idx


def top_k(x, k: int):
    """The ``k`` largest entries of ``x``'s last axis and their indices,
    largest first; equal values by ascending index (``lax.top_k``'s
    rule), independent of any batch dimensions."""
    if jax.default_backend() == "tpu":
        return top_k_sorted(x, k)
    return jax.lax.top_k(x, k)
