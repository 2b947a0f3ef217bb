"""One blocking read per sync point: device arrays packed, bit for bit,
into one int32 vector inside the program that computes them, and
unpacked on the host from that one transfer.

``pack32`` runs traced (inside a jitted program): 32-bit leaves are
bitcast, bools become 0/1. ``unpack32`` takes the host copy and the
leaves' shapes and dtypes (device arrays or ``ShapeDtypeStruct``s,
whose metadata needs no transfer) and returns numpy arrays equal to
``np.asarray`` of each leaf, dtype included."""
from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def pack32(leaves: Sequence) -> jnp.ndarray:
    """The leaves' bits, raveled and concatenated in order, as one
    int32 vector (traced)."""
    parts = []
    for x in leaves:
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)
        elif x.dtype.itemsize == 4:
            x = jax.lax.bitcast_convert_type(x, jnp.int32)
        else:
            raise TypeError(f"pack32 takes 32-bit or bool leaves, "
                            f"not {x.dtype}")
        parts.append(x.ravel())
    return jnp.concatenate(parts)


def unpack32(flat: np.ndarray, avals: Sequence) -> List[np.ndarray]:
    """Split the host copy of a ``pack32`` vector back into arrays of
    the ``avals``' shapes and dtypes."""
    out, off = [], 0
    for a in avals:
        n = math.prod(a.shape)
        seg = flat[off:off + n]
        off += n
        seg = seg != 0 if a.dtype == np.bool_ else seg.view(a.dtype)
        out.append(seg.reshape(a.shape))
    assert off == flat.size, (off, flat.size)
    return out
