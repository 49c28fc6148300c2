"""Plain helpers shared by the configurations' data makers and references.

Nothing here imports the program under test."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def data_key(seed: int, stream: int):
    """A JAX key for one stream of a run's data.  Seeds may exceed 32 bits:
    the high word is folded in, so no two seeds share their data."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), stream)


def service_seed(seed: int) -> int:
    """The seed handed to the service under test (its PRNGKey takes 31 bits)."""
    return int(seed) & 0x7FFFFFFF


def id_bits(n_objects: int) -> int:
    return max(1, int(n_objects - 1).bit_length())


def int_bytes(domain: int) -> int:
    """Bytes of the narrowest integer type that holds values in [0, domain)."""
    for width in (1, 2, 4):
        if domain <= 1 << (8 * width):
            return width
    return 8


def order_keys(counts, ids, n_objects: int, max_count: int,
               ties_descending: bool = False):
    """One int32 key per (count, id) whose descending order is count desc,
    then id asc (id desc with `ties_descending`).  Keys are unique, so a
    top-k over them has no ties left to break."""
    bits = id_bits(n_objects)
    if (max_count + 1) << bits > 1 << 31:
        raise ValueError(f"counts up to {max_count} and {n_objects} ids do "
                         f"not fit one int32 key")
    low = ids if ties_descending else (1 << bits) - 1 - ids
    return (counts << bits) | low


def decode_keys(keys, n_objects: int, ties_descending: bool = False):
    """(ids, counts) of keys made by `order_keys`."""
    bits = id_bits(n_objects)
    low = keys & ((1 << bits) - 1)
    ids = low if ties_descending else (1 << bits) - 1 - low
    return ids, keys >> bits


def merge_topk(parts: list, k: int):
    """Top-k keys of per-block top-k keys [q, k] (descending)."""
    return jax.lax.top_k(jnp.concatenate(parts, axis=1), k)[0]


def to_host(*arrays):
    return tuple(np.asarray(a) for a in arrays)
