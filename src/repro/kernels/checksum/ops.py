"""jit'd wrapper: any-dtype array -> (rows, 128) words -> device checksum."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...accel import pallas_interpret
from .kernel import LANES, checksum_words


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def device_checksum(x: jax.Array, *, block: int = 4096,
                    interpret=None) -> jax.Array:
    """Order-sensitive Fletcher-style checksum of an array's elements, each
    taken as one 32-bit word (floats by their float32 bits, integers by
    value mod 2^32). Returns (2,) uint32; ``interpret=None`` resolves per
    platform.

    8-bit arrays stay 8-bit until the kernel widens them, and an array
    that is already ``(rows, 128)`` with whole blocks is read in place —
    a multi-GB byte bundle is checked without a copy.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    if x.dtype.itemsize == 1:
        words = x
    elif jnp.issubdtype(x.dtype, jnp.floating):
        words = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    else:
        words = jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)
    n = words.size
    b = min(block, -(-n // LANES) * LANES)  # one block for small inputs
    pad = (-n) % b
    if pad:
        words = jnp.pad(words.reshape(-1), (0, pad))
    return checksum_words(words.reshape(-1, LANES), block=b,
                          interpret=interpret)


def verify_replicas(checksums) -> bool:
    """All hosts' checksums equal => replication fabric delivered identical
    bytes everywhere (cheap cross-host agreement check)."""
    import numpy as np

    arr = np.stack([np.asarray(c) for c in checksums])
    return bool((arr == arr[0]).all())
