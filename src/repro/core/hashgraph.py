"""Single-device HashGraph (Green [12]) — CSR hash table, TPU-native build.

A HashGraph stores a static hash table as the CSR of the bipartite graph
(hash values × keys):

* ``offsets`` — length ``V + 2``; bucket ``v``'s keys live at
  ``keys[offsets[v] : offsets[v+1]]``.  Bucket ``V`` is a *trash* bucket that
  holds padding sentinels (used when this table is one shard of a
  distributed HashGraph and the all-to-all delivered capacity padding).
* ``keys``   — the input keys grouped by bucket.
* ``values`` — payload per key (defaults to the original input index, the
  "value" the paper attaches for join operations).

TPU adaptation (see DESIGN.md §2): the CUDA build uses ``AtomicAdd`` for the
bucket histogram and for placement (Alg. 1).  TPUs expose no global-memory
atomics, so the build is a **counting sort realized with ``jax.lax.sort``**:
a stable lexicographic sort by (bucket, key) produces exactly the CSR
``keys`` array, and ``searchsorted`` over the sorted bucket ids produces
``offsets``.  The output is identical to the atomic build up to intra-bucket
order (which CUDA atomics leave nondeterministic; ours is deterministic).

Sorting *within* the bucket (``num_keys=2``) is a beyond-paper refinement:
it lets queries use per-bucket binary search (:func:`query_count_sorted`)
instead of the paper's linear bucket scan (:func:`query_count_probe`), which
matters once duplicate counts grow (paper §5.4 observes quadratic decay for
the linear-scan intersection).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.obs.tracing import stage

# Sentinel key marking capacity padding (reserved; valid keys must be < 2^32-1
# for 1-lane keys, < 2^64-1 for 2-lane packed keys — the sentinel is all-ones
# in every lane).
EMPTY_KEY = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Lane helpers — the schema layer (repro.core.schema) stores keys as (N,)
# uint32 or (N, L) packed uint32 lanes (lane 0 least significant) and values
# as (N,) or (N, C) int32.  Every routine below is polymorphic over both
# layouts; the 1-D forms are bit-identical to the original 32-bit path.
# ---------------------------------------------------------------------------


def is_empty_key(keys: jax.Array) -> jax.Array:
    """Padding-sentinel mask: all lanes equal ``EMPTY_KEY``."""
    if keys.ndim == 1:
        return keys == jnp.uint32(EMPTY_KEY)
    return jnp.all(keys == jnp.uint32(EMPTY_KEY), axis=-1)


def _cols(arr: jax.Array) -> tuple:
    """View a (N,) or (N, L) array as a tuple of (N,) lane/column arrays."""
    if arr.ndim == 1:
        return (arr,)
    return tuple(arr[:, i] for i in range(arr.shape[-1]))


def _from_cols(cols: Sequence, ndim: int) -> jax.Array:
    """Inverse of :func:`_cols` for the given original ndim."""
    if ndim == 1:
        return cols[0]
    return jnp.stack(cols, axis=-1)


def _lanes_lt_eq(a: tuple, b: tuple) -> tuple[jax.Array, jax.Array]:
    """Elementwise ``(a < b, a == b)`` for keys given as lane tuples.

    Lanes compare as packed big integers, lane ``L-1`` most significant
    (numeric uint64 order for the 2-lane packing).  Keys travel as 1-D
    lanes, never as ``(M, L)`` rows, through the large searches: XLA:TPU
    tiles an ``(M, L)`` intermediate with small ``L`` as ``(8, 128)`` rows,
    ``128 / L`` times its size.
    """
    lt, eq = a[-1] < b[-1], a[-1] == b[-1]
    for al, bl in zip(reversed(a[:-1]), reversed(b[:-1])):
        lt = lt | (eq & (al < bl))
        eq = eq & (al == bl)
    return lt, eq


def take_rows(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """``arr[idx]`` for ``(N,)`` or ``(N, C)`` arrays, one column at a time.

    A row gather from ``(N, C)`` with small ``C`` yields an intermediate
    XLA:TPU tiles as ``(8, 128)`` rows (``128 / C`` times its size, past
    HBM at 2^25 rows on v5e); column gathers keep it 1-D.
    """
    if arr.ndim == 1:
        return arr[idx]
    return jnp.stack([c[idx] for c in _cols(arr)], axis=-1)


def match_epochs(
    keys: jax.Array, ts_keys: jax.Array, ts_epochs: jax.Array
) -> jax.Array:
    """Newest tombstone epoch matching each key; ``-1`` where none match.

    ``keys`` is ``(M,)`` / ``(M, L)``; ``ts_keys`` a ``(T,)`` / ``(T, L)``
    tombstone buffer whose unused slots hold the EMPTY sentinel with epoch
    ``-1``.  A layer of the versioned table with epoch ``e`` must hide key
    ``k`` iff ``match_epochs(k) >= e`` — deletions mask every layer that
    existed when they were issued, and nothing inserted after.  ``O(M * T)``
    vectorized compares; the tombstone ring is small and bounded.
    """
    if ts_keys.shape[0] == 0:
        return jnp.full(keys.shape[:1], -1, jnp.int32)
    if keys.ndim == 1:
        eq = keys[:, None] == ts_keys[None, :]
    else:
        eq = jnp.all(keys[:, None, :] == ts_keys[None, :, :], axis=-1)
    stamped = jnp.where(eq, ts_epochs[None, :].astype(jnp.int32), jnp.int32(-1))
    return jnp.max(stamped, axis=1)


def sort_tombstones(
    ts_keys: jax.Array, ts_epochs: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Sort a tombstone buffer by (key, epoch) for binary-search lookup.

    Duplicate keys (the same key deleted at several epochs) sort with epochs
    ascending, so the *last* entry of a key's run carries its newest epoch —
    exactly what :func:`match_epochs_sorted` reads.  Unused slots (EMPTY key,
    epoch ``-1``) sort to the end: EMPTY is the maximal key value and valid
    keys are required to be strictly smaller.
    """
    if ts_keys.shape[0] == 0:
        return ts_keys, ts_epochs
    key_cols = _cols(ts_keys)
    sort_ops = tuple(reversed(key_cols))  # most-significant lane first
    # Every operand is a sort key, so equal entries are identical and a
    # stable sort (twice the TPU compile time) would change nothing.
    out = jax.lax.sort(
        (*sort_ops, ts_epochs.astype(jnp.int32)),
        num_keys=len(sort_ops) + 1,
        is_stable=False,
    )
    sorted_keys = _from_cols(tuple(reversed(out[: len(key_cols)])), ts_keys.ndim)
    return sorted_keys, out[-1]


def match_epochs_sorted(
    keys: jax.Array, ts_keys: jax.Array, ts_epochs: jax.Array
) -> jax.Array:
    """Newest tombstone epoch matching each key; ``-1`` where none match.

    Sorted-index counterpart of :func:`match_epochs`: ``ts_keys``/``ts_epochs``
    must come from :func:`sort_tombstones` (keys ascending, epochs ascending
    within duplicate-key runs).  One branchless bisection per key —
    ``O(M log T)`` instead of the broadcast compare's ``O(M * T)`` — which is
    what keeps tombstone masking off the critical path for large delete
    volumes (ROADMAP "tombstone scaling").
    """
    t = ts_keys.shape[0]
    if t == 0:
        return jnp.full(keys.shape[:1], -1, jnp.int32)
    m = keys.shape[0]
    lo = jnp.zeros((m,), jnp.int32)
    hi = jnp.full((m,), t, jnp.int32)
    ts_lanes, key_lanes = _cols(ts_keys), _cols(keys)
    right = _segment_searchsorted(ts_lanes, lo, hi, key_lanes, side="right")
    idx = jnp.clip(right - 1, 0, t - 1)
    _, eq = _lanes_lt_eq(tuple(c[idx] for c in ts_lanes), key_lanes)
    hit = (right > 0) & eq
    return jnp.where(hit, ts_epochs[idx].astype(jnp.int32), jnp.int32(-1))


def rows_equal(a: jax.Array, b: jax.Array) -> jax.Array:
    """Row equality for 1-D or multi-lane key arrays (broadcasting)."""
    if a.ndim == 1 and b.ndim == 1:
        return a == b
    if a.ndim == 1 or b.ndim == 1:
        raise ValueError("cannot compare 1-lane with multi-lane keys")
    return jnp.all(a == b, axis=-1)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("offsets", "keys", "values", "fingerprints"),
    meta_fields=("table_size", "seed", "sorted_within_bucket"),
)
@dataclasses.dataclass(frozen=True)
class HashGraph:
    """CSR hash table.  ``offsets.shape == (table_size + 2,)``.

    When ``fingerprints`` is present the rows of a bucket are ordered by
    ``(fingerprint, key)`` instead of plain ``(key)``: the probe path
    bisects the single-lane fingerprint array first and touches the full
    key lanes only inside the (typically 0- or 1-key) run of rows whose
    fingerprint matched — the compact-probe layout of "Compact Parallel
    Hash Tables on the GPU".  Occurrences of one key stay contiguous
    either way (equal keys share a fingerprint), so every CSR invariant
    and the multiset query semantics are unchanged.
    """

    offsets: jax.Array  # (V+2,) int32, monotone
    keys: jax.Array  # (N,) uint32 or (N, L) packed lanes, grouped by bucket
    values: jax.Array  # (N,) or (N, C) int32 payload
    table_size: int  # V (static)
    seed: int  # murmur seed (static)
    sorted_within_bucket: bool  # True => binary-search queries are valid
    fingerprints: Optional[jax.Array] = None  # (N,) uint32 probe lane, or None

    @property
    def capacity(self) -> int:
        return int(self.keys.shape[0])

    @property
    def key_lanes(self) -> int:
        return 1 if self.keys.ndim == 1 else int(self.keys.shape[-1])

    @property
    def value_cols(self) -> int:
        return 1 if self.values.ndim == 1 else int(self.values.shape[-1])

    @property
    def num_valid(self) -> jax.Array:
        """Number of non-padding keys (start of the trash bucket)."""
        return self.offsets[self.table_size]

    def bucket_of(self, queries: jax.Array) -> jax.Array:
        return hashing.hash_to_buckets(queries, self.table_size, seed=self.seed)


def build_from_buckets(
    keys: jax.Array,
    buckets: jax.Array,
    table_size: int,
    values: Optional[jax.Array] = None,
    *,
    seed: int = hashing.DEFAULT_SEED,
    sort_within_bucket: bool = True,
    fingerprint: Optional[bool] = None,
) -> HashGraph:
    """Build a HashGraph given precomputed bucket ids.

    ``buckets`` may contain ``table_size`` to mark padding entries (they land
    in the trash bucket and are excluded from every query).

    ``fingerprint=None`` (auto) stores a probe fingerprint lane exactly when
    the keys are multi-lane — where the fingerprint halves (or better) the
    bytes the sorted search touches.  ``True``/``False`` force it.  A
    fingerprint lane requires ``sort_within_bucket`` (the linear-probe
    layout never bisects, so the lane would be dead weight); it is dropped
    silently otherwise.
    """
    with stage("build.sort"):
        keys = keys.astype(jnp.uint32)
        buckets = buckets.astype(jnp.int32)
        if values is None:
            values = jnp.arange(keys.shape[0], dtype=jnp.int32)
        if fingerprint is None:
            fingerprint = keys.ndim == 2
        fingerprint = bool(fingerprint) and sort_within_bucket
        # Lexicographic sort by (bucket, [fingerprint,] key) with multi-lane
        # keys compared as packed big integers: lane L-1 (most significant)
        # first, lane 0 last.  With the fingerprint lane enabled the
        # within-bucket order is (fp, key) — equal keys share a fingerprint,
        # so per-key runs stay contiguous.  A row index is the last sort key:
        # it keeps equal keys in input order (what a stable sort gives, while
        # the sort itself may be unstable) and yields the permutation that
        # gathers the payload afterwards.  Payload columns do not ride
        # through the sort: a TPU sort's compile time grows with every
        # operand it carries (about 20 s each at 2^25 rows on v5e, twice that
        # for a stable sort), a gather's does not.
        key_cols = _cols(keys)
        fp_ops: tuple = ()
        if fingerprint:
            fp_ops = (hashing.fingerprint32(keys),)
        sort_key_ops = (*fp_ops, *reversed(key_cols)) if sort_within_bucket else ()
        rows = jnp.arange(keys.shape[0], dtype=jnp.int32)
        out = jax.lax.sort(
            (buckets, *sort_key_ops, rows),
            num_keys=len(sort_key_ops) + 2,
            is_stable=False,  # the row index already makes the order total
        )
        sorted_buckets = out[0]
        perm = out[-1]
        nf = len(fp_ops)
        sorted_fp = out[1] if fingerprint else None
        if sort_within_bucket:
            sorted_keys = _from_cols(
                tuple(reversed(out[1 + nf : 1 + nf + len(key_cols)])), keys.ndim
            )
        else:
            sorted_keys = take_rows(keys, perm)
        sorted_values = take_rows(values, perm)
    with stage("build.offsets"):
        # offsets[v] = first index whose bucket id >= v ;  offsets[V+1] = N.
        offsets = jnp.searchsorted(
            sorted_buckets, jnp.arange(table_size + 2, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
    return HashGraph(
        offsets=offsets,
        keys=sorted_keys,
        values=sorted_values,
        table_size=table_size,
        seed=seed,
        sorted_within_bucket=sort_within_bucket,
        fingerprints=sorted_fp,
    )


def build(
    keys: jax.Array,
    table_size: int,
    values: Optional[jax.Array] = None,
    *,
    seed: int = hashing.DEFAULT_SEED,
    sort_within_bucket: bool = True,
    fingerprint: Optional[bool] = None,
) -> HashGraph:
    """Hash ``keys`` and build the CSR table (Alg. 1, TPU-native form)."""
    buckets = hashing.hash_to_buckets(keys, table_size, seed=seed)
    return build_from_buckets(
        keys,
        buckets,
        table_size,
        values,
        seed=seed,
        sort_within_bucket=sort_within_bucket,
        fingerprint=fingerprint,
    )


# ---------------------------------------------------------------------------
# Queries
# ---------------------------------------------------------------------------


def _segment_searchsorted(
    sorted_keys,
    lo: jax.Array,
    hi: jax.Array,
    q,
    side: str,
) -> jax.Array:
    """Vectorized binary search of ``q[i]`` within ``sorted_keys[lo[i]:hi[i]]``.

    Branchless bisection with a fixed iteration count (log2 of array size),
    so it lowers to a small unrolled loop of gathers — no data-dependent
    control flow, TPU-friendly.  Multi-lane keys compare as packed big
    integers (lane L-1 most significant).

    ``sorted_keys`` and ``q`` are arrays, or both tuples of 1-D lanes.  The
    array form gathers whole key rows at ``mid`` — right for a small query
    batch against a large table.  A large ``q`` against a small sorted
    array (tombstone masking of a whole layer) passes lanes: each step
    then gathers 1-D lanes and no ``(len(q), L)`` intermediate exists (see
    :func:`_lanes_lt_eq`).
    """
    lanes = isinstance(sorted_keys, tuple)
    n = (sorted_keys[0] if lanes else sorted_keys).shape[0]
    # A range of length L needs bit_length(L) halvings to reach lo == hi
    # (bit_length(n-1) is one short when a bucket spans the whole array —
    # found by hypothesis on a 2-key table with both keys in one bucket).
    iters = max(1, int(n).bit_length())
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    q_lanes = q if lanes else _cols(q)

    def body(_, lohi):
        lo, hi = lohi
        mid = jnp.clip((lo + hi) >> 1, 0, n - 1)
        if lanes:
            v = tuple(c[mid] for c in sorted_keys)
        else:
            v = _cols(sorted_keys[mid])
        v_lt, v_eq = _lanes_lt_eq(v, q_lanes)
        go_right = v_lt if side == "left" else (v_lt | v_eq)
        active = lo < hi
        new_lo = jnp.where(active & go_right, mid + 1, lo)
        new_hi = jnp.where(active & ~go_right, mid, hi)
        return new_lo, new_hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def query_locate(
    hg: HashGraph,
    queries: jax.Array,
    buckets: Optional[jax.Array] = None,
    qfp: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Locate each query's match run: ``(starts, counts)``.

    All occurrences of a key are contiguous in a bucket-sorted HashGraph, so
    a query's matches are exactly ``hg.keys[starts[i] : starts[i]+counts[i]]``
    (and its payloads the same slice of ``hg.values``).  This is the counting
    pass of the two-pass count→prefix-sum→gather retrieval pipeline.

    ``buckets`` overrides the bucket mapping (distributed shards map keys to
    local buckets through the global split points, not ``hash % V``).

    When the table carries a fingerprint lane, the bucket window is bisected
    on the single-lane uint32 fingerprints first; the full key lanes are
    only gathered by the verification bisection *inside* the fingerprint
    run, which resolves fingerprint collisions exactly.  ``qfp`` supplies
    precomputed query fingerprints (the fused distributed route hashes each
    routed batch once and probes every layer with it); left ``None`` they
    are derived here.  Ignored for tables without the lane.
    """
    if not hg.sorted_within_bucket:
        raise ValueError("query_locate needs a bucket-sorted HashGraph")
    q = queries.astype(jnp.uint32)
    b = hg.bucket_of(q) if buckets is None else buckets.astype(jnp.int32)
    starts = hg.offsets[b]
    ends = hg.offsets[b + 1]
    if hg.fingerprints is not None:
        if qfp is None:
            qfp = hashing.fingerprint32(q)
        qfp = qfp.astype(jnp.uint32)
        fl = _segment_searchsorted(hg.fingerprints, starts, ends, qfp, side="left")
        fr = _segment_searchsorted(hg.fingerprints, starts, ends, qfp, side="right")
        # Verification pass: exact key bisection confined to [fl, fr) — the
        # run of rows whose fingerprint matched (usually 0 or 1 distinct key).
        starts, ends = fl, fr
    left = _segment_searchsorted(hg.keys, starts, ends, q, side="left")
    right = _segment_searchsorted(hg.keys, starts, ends, q, side="right")
    return left.astype(jnp.int32), (right - left).astype(jnp.int32)


def query_count_sorted(
    hg: HashGraph,
    queries: jax.Array,
    buckets: Optional[jax.Array] = None,
    qfp: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact multiplicity of each query key via per-bucket binary search.

    Requires ``sorted_within_bucket=True``.  O(log bucket_len) gathers per
    query with no cap on duplicates — the beyond-paper query path.
    """
    _, counts = query_locate(hg, queries, buckets, qfp=qfp)
    return counts


def csr_gather(
    starts: jax.Array,
    counts: jax.Array,
    table: jax.Array,
    capacity: int,
    *,
    fill=jnp.int32(-1),
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Second pass of the retrieval pipeline: CSR compaction of match runs.

    Row ``i`` owns ``table[starts[i] : starts[i]+counts[i]]``; the runs are
    concatenated row-major into a static ``(capacity,)`` buffer (HashGraph's
    CSR-build idiom applied to the *output*: prefix-sum the counts, expand
    the rows onto the output slots, then one vectorized gather fills every
    slot).  The expansion scatters a marker per row at the slot where its
    run begins and prefix-sums the markers over the slots: O(capacity + N),
    with no search of the offsets per slot.

    Returns ``(offsets, row_idx, gathered, num_dropped)``:

    * ``offsets``  — ``(N+1,)`` int32, clamped to ``capacity``; row ``i``'s
      results are ``gathered[offsets[i]:offsets[i+1]]``.
    * ``row_idx``  — ``(capacity,)`` int32, source row per output slot
      (``-1`` in unused slots).
    * ``gathered`` — ``(capacity,)`` (or ``(capacity, C)`` when ``table``
      has payload columns) same dtype as ``table``; unused slots carry
      ``fill``.
    * ``num_dropped`` — ``()`` int32, ``max(0, total - capacity)``.  Overflow
      is *reported*, never silent: callers must treat ``num_dropped > 0`` as
      "re-run with a larger capacity".
    """
    counts = counts.astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)]
    )
    total = offsets[-1]
    slot = jnp.arange(capacity, dtype=jnp.int32)
    # Slot s belongs to the last row whose offset is <= s, and reads
    # table[s + shift[row]].  Each row marks the slot where its run begins:
    # with a row step of 1 (row 0 needs none) and with the change of shift
    # from the row before.  Prefix sums of the marks give every slot its
    # row and shift.  Zero-count rows share their successor's slot, so
    # their steps add up and their shifts telescope away; marks at or past
    # capacity are dropped, as no slot reads them.  (Two 1-D scatters, not
    # one of a (2, capacity) operand, which XLA:TPU scatters ~6x slower.)
    def spread(at, marks):
        return jnp.cumsum(
            jnp.zeros((capacity,), jnp.int32)
            .at[at]
            .add(marks, mode="drop", indices_are_sorted=True)
        )

    shift = starts.astype(jnp.int32) - offsets[:-1]
    row = spread(offsets[1:-1], jnp.int32(1))
    src = slot + spread(offsets[:-1], jnp.diff(shift, prepend=0))
    valid = slot < total
    tn = table.shape[0]
    # table may carry trailing payload columns (N, C); broadcast the mask.
    valid_b = valid.reshape((-1,) + (1,) * (table.ndim - 1))
    gathered = jnp.where(
        valid_b, table[jnp.clip(src, 0, tn - 1)], jnp.asarray(fill, table.dtype)
    )
    row_idx = jnp.where(valid, row, jnp.int32(-1))
    num_dropped = jnp.maximum(total - capacity, 0).astype(jnp.int32)
    return jnp.minimum(offsets, capacity), row_idx, gathered, num_dropped


def retrieve(
    hg: HashGraph,
    queries: jax.Array,
    *,
    capacity: int,
    buckets: Optional[jax.Array] = None,
    fill=jnp.int32(-1),
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Values stored under every occurrence of every query key, CSR-shaped.

    Two-pass count→prefix-sum→gather (the HashGraph build idiom, §3.2,
    applied to the query side — the WarpSpeed-style retrieval API).  Returns
    ``(offsets, values, num_dropped)`` with ``offsets`` of shape
    ``(len(queries)+1,)``: query ``i``'s values are
    ``values[offsets[i]:offsets[i+1]]`` (within-key order is the table's
    deterministic bucket order, not insertion order).  ``capacity`` is the
    static output size; overflow is reported via ``num_dropped``.
    """
    starts, counts = query_locate(hg, queries, buckets)
    offsets, _, values, num_dropped = csr_gather(
        starts, counts, hg.values, capacity, fill=fill
    )
    return offsets, values, num_dropped


def inner_join(
    hg: HashGraph,
    queries: jax.Array,
    *,
    capacity: int,
    buckets: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Materialized inner join: every ``(query_idx, build_value)`` match pair.

    Returns ``(query_idx, values, num_results, num_dropped)``, each output
    array of shape ``(capacity,)`` with ``-1`` / fill beyond ``num_results``.
    """
    starts, counts = query_locate(hg, queries, buckets)
    _, query_idx, values, num_dropped = csr_gather(
        starts, counts, hg.values, capacity
    )
    num_results = jnp.minimum(jnp.sum(counts), capacity).astype(jnp.int32)
    return query_idx, values, num_results, num_dropped


def query_count_probe(
    hg: HashGraph,
    queries: jax.Array,
    max_probe: int = 64,
    buckets: Optional[jax.Array] = None,
) -> jax.Array:
    """Paper-faithful query: linear scan of the query's bucket.

    ``max_probe`` statically caps the scanned bucket length (buckets longer
    than the cap under-count — callers size the cap from the duplicate
    statistics, as the paper sizes its experiments).  This is the access
    pattern the ``bucket_probe`` Pallas kernel implements in VMEM blocks.
    """
    q = queries.astype(jnp.uint32)
    b = hg.bucket_of(q) if buckets is None else buckets.astype(jnp.int32)
    starts = hg.offsets[b]
    ends = hg.offsets[b + 1]
    n = hg.keys.shape[0]
    idx = starts[:, None] + jnp.arange(max_probe, dtype=jnp.int32)[None, :]
    in_bucket = idx < ends[:, None]
    vals = hg.keys[jnp.clip(idx, 0, n - 1)]
    if q.ndim == 1:
        eq = vals == q[:, None]  # (nq, max_probe)
    else:
        eq = jnp.all(vals == q[:, None, :], axis=-1)  # lanes reduced
    hits = in_bucket & eq
    return jnp.sum(hits, axis=1).astype(jnp.int32)


def lookup_first(
    hg: HashGraph, queries: jax.Array, buckets: Optional[jax.Array] = None
) -> jax.Array:
    """Value row of the first matching key per query, or -1 fill (join probe).

    Returns ``(Nq,)`` int32 for single-column payloads, ``(Nq, C)`` for
    multi-column (every column filled with -1 on a miss).
    """
    if not hg.sorted_within_bucket:
        raise ValueError("lookup_first needs a bucket-sorted HashGraph")
    q = queries.astype(jnp.uint32)
    b = hg.bucket_of(q) if buckets is None else buckets.astype(jnp.int32)
    starts = hg.offsets[b]
    ends = hg.offsets[b + 1]
    if hg.fingerprints is not None:
        qfp = hashing.fingerprint32(q)
        starts = _segment_searchsorted(
            hg.fingerprints, starts, ends, qfp, side="left"
        )
        ends = _segment_searchsorted(
            hg.fingerprints, starts, ends, qfp, side="right"
        )
    left = _segment_searchsorted(hg.keys, starts, ends, q, side="left")
    n = hg.keys.shape[0]
    found = (left < ends) & rows_equal(hg.keys[jnp.clip(left, 0, n - 1)], q)
    found_b = found.reshape((-1,) + (1,) * (hg.values.ndim - 1))
    return jnp.where(found_b, hg.values[jnp.clip(left, 0, n - 1)], jnp.int32(-1))


def contains(hg: HashGraph, queries: jax.Array) -> jax.Array:
    """Membership test per query key."""
    return query_count_sorted(hg, queries) > 0


def intersect_join_size(hg_build: HashGraph, hg_query: HashGraph) -> jax.Array:
    """Total inner-join size between two HashGraphs sharing a bucket space.

    The paper's query phase (§3.3): for every key in the query table, count
    its occurrences in the build table; the sum is the join cardinality.
    Padding (trash-bucket) entries contribute zero.
    """
    valid = jnp.arange(hg_query.keys.shape[0]) < hg_query.num_valid
    counts = query_count_sorted(hg_build, hg_query.keys)
    return jnp.sum(jnp.where(valid, counts, 0).astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32))
