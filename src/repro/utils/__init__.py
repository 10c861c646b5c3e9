"""Small shared utilities: dtypes, pytree helpers, counting."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.config import configure, host_device_count

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int8": jnp.int8,
    "int32": jnp.int32,
}


def dtype_of(name: str):
    return DTYPES[name]


def dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def tree_size(tree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    """Total bytes across all leaves (works on ShapeDtypeStructs too)."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    )


def tree_allfinite(tree) -> bool:
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    if not leaves:
        return True
    return bool(jnp.all(jnp.stack(leaves)))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"
