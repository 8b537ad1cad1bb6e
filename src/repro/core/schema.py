"""Table schema — key width and payload shape for the HashGraph stack.

The paper targets 32-bit keys with a single int32 payload; its headline
applications (database joins, DNA k-mers) need 64-bit keys and wider
payloads.  A :class:`TableSchema` names the key dtype (``uint32`` or
``uint64``) and the number of int32 payload columns; the whole
build/query/retrieve/join data path is polymorphic over it.

Representation
--------------
JAX on TPU has no native 64-bit integer lanes (and ``jax_enable_x64`` is
off by default), so a 64-bit key is stored **packed as two uint32 lanes**:

* 1-lane keys: a ``(N,)`` uint32 array — the paper's layout, unchanged.
* 2-lane keys: a ``(N, 2)`` uint32 array with ``[:, 0]`` the low word and
  ``[:, 1]`` the high word (little-endian word order, matching the
  4-byte-block order MurmurHash3_x86_32 consumes — see
  ``hashing.murmur3_packed``).

Payloads are ``(N,)`` int32 for a single column or ``(N, C)`` int32 for
``C`` columns.  Every core routine accepts either layout; the 1-D forms
are the exact PR-1 API and stay bit-identical.

Host-side packing helpers (``pack_u64`` / ``unpack_u64``) convert numpy
uint64 arrays to and from the two-lane layout without ever materializing
64-bit integers on device.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

_KEY_DTYPES = ("uint32", "uint64")


def pack_u64(keys) -> jax.Array:
    """Host-side: numpy uint64 (or python ints) ``(N,)`` → ``(N, 2)`` uint32.

    Lane 0 is the low 32 bits, lane 1 the high 32 bits.
    """
    return jnp.asarray(_pack_u64_host(keys))


def _pack_u64_host(keys) -> np.ndarray:
    a = np.asarray(keys, dtype=np.uint64)
    lo = (a & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (a >> np.uint64(32)).astype(np.uint32)
    return np.stack([lo, hi], axis=-1)


def _place(x, sharding):
    """A device array of ``x``: on ``sharding`` when given, else the default
    device.  Host arrays are transferred once, straight to their shards;
    traced values are left to the enclosing program's shardings."""
    if sharding is None or isinstance(x, jax.core.Tracer):
        return jnp.asarray(x)
    return jax.device_put(x, sharding)


def unpack_u64(packed) -> np.ndarray:
    """Host-side inverse of :func:`pack_u64`: ``(N, 2)`` uint32 → np.uint64."""
    a = np.asarray(packed)
    lo = a[..., 0].astype(np.uint64)
    hi = a[..., 1].astype(np.uint64)
    return (hi << np.uint64(32)) | lo


@dataclasses.dataclass(frozen=True)
class TableSchema:
    """Key width + payload shape of one hash table.

    ``key_dtype`` — ``"uint32"`` (1 lane) or ``"uint64"`` (2 packed lanes).
    ``value_cols`` — number of int32 payload columns (1 keeps the PR-1
    1-D layout; >1 stores ``(N, C)``).
    """

    key_dtype: str = "uint32"
    value_cols: int = 1

    def __post_init__(self):
        if self.key_dtype not in _KEY_DTYPES:
            raise ValueError(
                f"key_dtype must be one of {_KEY_DTYPES}, got {self.key_dtype!r}"
            )
        if not 1 <= int(self.value_cols):
            raise ValueError(f"value_cols must be >= 1, got {self.value_cols}")

    @property
    def key_lanes(self) -> int:
        return 2 if self.key_dtype == "uint64" else 1

    # -- canonicalization ----------------------------------------------------
    def host_keys(self, keys) -> np.ndarray:
        """Canonical packed keys on the host: ``(N,)`` or ``(N, 2)`` uint32.

        Accepts numpy arrays or python ints (64-bit values are split into
        lanes for the uint64 schema) and device arrays (fetched).
        """
        keys = np.asarray(keys)
        if keys.dtype in (np.uint64, np.int64):
            if self.key_lanes == 2:
                if keys.dtype == np.int64 and (keys < 0).any():
                    raise ValueError("uint64 schema got negative int64 keys")
                keys = _pack_u64_host(keys)
            elif (keys < 0).any() or (keys > 0xFFFFFFFF).any():
                # 1-lane schema: reject wide values instead of wrapping mod 2^32.
                raise ValueError(
                    "uint32 schema got 64-bit key values out of range; "
                    "use TableSchema('uint64')"
                )
        keys = keys.astype(np.uint32)
        self._check_key_shape(keys.shape)
        return keys

    def pack_keys(self, keys, sharding=None) -> jax.Array:
        """Canonical device layout: ``(N,)`` uint32 or ``(N, 2)`` uint32.

        Accepts host arrays (canonicalized by :meth:`host_keys`) or
        already-packed device arrays; validates the lane count.  With
        ``sharding`` the result is placed on it (host rows go straight to
        their shards, never through one device).
        """
        if isinstance(keys, jax.Array):
            keys = keys.astype(jnp.uint32)
            self._check_key_shape(keys.shape)
        else:
            keys = self.host_keys(keys)
        return _place(keys, sharding)

    def _check_key_shape(self, shape) -> None:
        if self.key_lanes == 1:
            if len(shape) != 1:
                raise ValueError(f"uint32 schema expects (N,) keys, got shape {shape}")
        elif len(shape) != 2 or shape[-1] != 2:
            raise ValueError(
                f"uint64 schema expects (N, 2) packed uint32 keys "
                f"(see schema.pack_u64), got shape {shape}"
            )

    def host_values(self, values) -> np.ndarray:
        """Canonical payload on the host: ``(N,)`` or ``(N, C)`` int32."""
        return self._check_values(np.asarray(values).astype(np.int32))

    def pack_values(self, values, sharding=None) -> jax.Array:
        """Canonical payload layout: ``(N,)`` or ``(N, C)`` int32, placed on
        ``sharding`` when given (see :meth:`pack_keys`)."""
        if isinstance(values, jax.Array):
            values = self._check_values(values.astype(jnp.int32))
        else:
            values = self.host_values(values)
        return _place(values, sharding)

    def default_values(self, n: int) -> np.ndarray:
        """Row-id payload for ``n`` rows, repeated across every column."""
        values = np.arange(n, dtype=np.int32)
        if self.value_cols > 1:
            values = np.stack([values] * self.value_cols, axis=1)
        return values

    def _check_values(self, values):
        if self.value_cols == 1:
            if values.ndim == 2 and values.shape[-1] == 1:
                values = values[:, 0]
            if values.ndim != 1:
                raise ValueError(
                    f"1-column schema expects (N,) values, got {values.shape}"
                )
        elif values.ndim != 2 or values.shape[-1] != self.value_cols:
            raise ValueError(
                f"schema expects (N, {self.value_cols}) values, "
                f"got shape {values.shape}"
            )
        return values
