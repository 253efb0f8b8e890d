"""JAX environment setup — imported by every device-facing module.

Device aggregation of scaled-int decimals and packed datetimes requires
64-bit lanes; XLA:TPU lowers s64 via 32-bit pairs, which is acceptable for
the reduction tails (the hot loops are f32/i32). Centralizing the config
here keeps `import tidb_tpu` (and the pure-host modules: mysqltypes, codec,
chunk, parser, planner) jax-free.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: cop/MPP programs are keyed by DAG
# digest in-process, but across processes (server restart, bench runs,
# the driver) recompiling identical programs costs seconds to a minute
# each on the TPU. The on-disk cache makes warmup a read (ref: the
# jit-cache story of copr/coprocessor_cache.go, taken one level down the
# stack). JAX_COMPILATION_CACHE_DIR, when set, places the cache and
# nothing here overrides it; otherwise it lives at one fixed path inside
# the checkout (the path is part of the cache key, so it never moves).
# A cache directory that cannot be made is an error, not a silent
# cold start.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
    )
    os.makedirs(_cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from jax import numpy as jnp  # noqa: E402  (re-export for device modules)

__all__ = ["jax", "jnp"]


# --------------------------------------------------------------------------
# single-buffer device→host result packing
#
# Every array fetched from the device is a separate device→host sync
# (its cost is not measured on the current stack), so multi-output
# programs ship one int64 matrix instead. Row 0 carries per-row dtype tags
# IN-BAND: jit keeps one executable per input-dtype signature, and any
# out-of-band metadata recorded at trace time goes stale when signatures
# alternate over the same compiled-program cache entry.
# --------------------------------------------------------------------------

_KIND_I64, _KIND_F64, _KIND_BOOL, _KIND_U64 = 0, 1, 2, 3


def pack_rows(outs):
    """[array (L,)] (int/float/bool/uint64) → one int64 matrix (n+1, L)
    whose row 0 holds the dtype tags. All arrays must share length L ≥
    len(outs)."""
    import numpy as _np

    rows, kinds = [], []
    for o in outs:
        if o.dtype == jnp.float32:
            o = o.astype(jnp.float64)
        if o.dtype == jnp.float64:
            kinds.append(_KIND_F64)
            rows.append(jax.lax.bitcast_convert_type(o, jnp.int64))
        elif o.dtype == jnp.uint64:
            kinds.append(_KIND_U64)
            rows.append(jax.lax.bitcast_convert_type(o, jnp.int64))
        elif o.dtype == jnp.bool_:
            kinds.append(_KIND_BOOL)
            rows.append(o.astype(jnp.int64))
        else:
            kinds.append(_KIND_I64)
            rows.append(o.astype(jnp.int64))
    L = rows[0].shape[0]
    need = len(kinds) + 1
    if L < need:  # tiny result rows (top-k): widen so the tags fit
        rows = [jnp.concatenate([r, jnp.zeros((need - L,), jnp.int64)]) for r in rows]
        L = need
    tag = _np.zeros(L, dtype=_np.int64)
    tag[: len(kinds)] = kinds
    tag[-1] = len(kinds)  # row count, so unpack needs no side channel
    return jnp.stack([jnp.asarray(tag)] + rows)


def unpack_rows(packed):
    """Inverse of pack_rows over the fetched numpy matrix."""
    import numpy as _np

    tag = packed[0]
    n = int(tag[-1])
    out = []
    for i in range(n):
        row = packed[1 + i]
        k = int(tag[i])
        if k == _KIND_F64:
            out.append(row.view(_np.float64))
        elif k == _KIND_U64:
            out.append(row.view(_np.uint64))
        elif k == _KIND_BOOL:
            out.append(row != 0)
        else:
            out.append(row)
    return out


def pack_flat(outs):
    """Variable-length single-buffer packing: [header | seg0 | seg1 | ...]
    as one int64 vector. Bool lanes ship bit-packed (64 rows/word) — for
    full-row results the valid lane would otherwise double the transfer.
    Header: [n, kind0, len0, kind1, len1, ...] (static length)."""
    import numpy as _np

    header = [len(outs)]
    segs = []
    for o in outs:
        if o.dtype == jnp.float32:
            o = o.astype(jnp.float64)
        if o.dtype == jnp.float64:
            kind = _KIND_F64
            seg = jax.lax.bitcast_convert_type(o, jnp.int64)
        elif o.dtype == jnp.uint64:
            kind = _KIND_U64
            seg = jax.lax.bitcast_convert_type(o, jnp.int64)
        elif o.dtype == jnp.bool_:
            kind = _KIND_BOOL
            L = o.shape[0]
            W = -(-L // 64)
            padded = jnp.concatenate([o, jnp.zeros((W * 64 - L,), bool)])
            bits = padded.reshape(W, 64).astype(jnp.uint64) << jnp.arange(64, dtype=jnp.uint64)[None, :]
            seg = jax.lax.bitcast_convert_type(jnp.sum(bits, axis=1, dtype=jnp.uint64), jnp.int64)
            header += [kind, int(L)]
            segs.append(seg)
            continue
        else:
            kind = _KIND_I64
            seg = o.astype(jnp.int64)
        header += [kind, int(seg.shape[0])]
        segs.append(seg)

    return jnp.concatenate([jnp.asarray(_np.asarray(header, dtype=_np.int64))] + segs)


def unpack_flat(flat):
    """Inverse of pack_flat over the fetched numpy vector."""
    import numpy as _np

    n = int(flat[0])
    pos = 1 + 2 * n
    out = []
    for i in range(n):
        kind = int(flat[1 + 2 * i])
        L = int(flat[2 + 2 * i])
        if kind == _KIND_BOOL:
            W = -(-L // 64)
            words = flat[pos : pos + W].view(_np.uint64)
            bits = _np.unpackbits(words.view(_np.uint8), bitorder="little")
            out.append(bits[:L].astype(bool))
            pos += W
        else:
            seg = flat[pos : pos + L]
            if kind == _KIND_F64:
                out.append(seg.view(_np.float64))
            elif kind == _KIND_U64:
                out.append(seg.view(_np.uint64))
            else:
                out.append(seg)
            pos += L
    return out
