"""Mesh-parallel cop execution (SURVEY §2.13, §5.8 — the TPU-native
replacement for region-parallel cop fan-out and TiFlash MPP exchange).

Mapping (reference mechanism → mesh construct):
  region-parallel scan (copr/coprocessor.go:151)   → rows sharded over the
      "dp" mesh axis; each device runs the fused scan/filter/partial-agg
      kernel on its shard
  partial/final agg split (aggregation descriptors) → local segmented-sum
      partials + `psum` over "dp" — exact for scaled-int decimals
  MPP hash exchange (cophandler/mpp_exec.go:109)    → `all_to_all` over the
      mesh axis after bucketing rows by key hash (hash_repartition)

Everything is jit-compiled once per (shape, mesh) and runs identically on
one real TPU, a v4-8 slice, or the virtual CPU mesh used in tests.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

from ..jaxenv import jax, jnp
from ..kernels.primitives import seg_sum
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_US_DAY = 24 * 60 * 60 * 1_000_000


def make_mesh(n_devices: int | None = None, axis: str = "dp") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


@dataclass(frozen=True)
class Q1Spec:
    """Static spec of the fused Q1 cop program (the flagship kernel)."""

    nseg: int = 8  # |returnflag dict| x |linestatus dict| padded (3*2 → 8)
    cutoff: int = 0  # packed shipdate cutoff (constant folded into program)


def q1_local_kernel(spec: Q1Spec, qty, price, disc, tax, rf, ls, ship, row_valid):
    """One shard's fused Q1: filter → group codes → partial segment sums.

    All decimal lanes are scaled int64 (scale 2); products carry scale 4/6.
    Output: tuple of [nseg] partial states (count, sums...), exact ints.
    """
    mask = row_valid & (ship <= spec.cutoff)
    code = rf * 2 + ls  # dict codes: rf in {0,1,2}, ls in {0,1}
    seg = jnp.where(mask, code, spec.nseg)  # masked rows → overflow slot

    def ssum(x):
        return seg_sum(x, seg, spec.nseg)

    m64 = mask.astype(jnp.int64)
    disc_price = price * (100 - disc)  # scale 4
    charge = disc_price * (100 + tax)  # scale 6
    return (
        ssum(m64),  # count
        ssum(jnp.where(mask, qty, 0)),  # sum_qty (s2)
        ssum(jnp.where(mask, price, 0)),  # sum_base_price (s2)
        ssum(jnp.where(mask, disc_price, 0)),  # sum_disc_price (s4)
        ssum(jnp.where(mask, charge, 0)),  # sum_charge (s6)
        ssum(jnp.where(mask, disc, 0)),  # sum_disc (s2, for avg)
    )


def distributed_q1_step(mesh: Mesh, spec: Q1Spec, axis: str = "dp"):
    """The full distributed step: shard rows over `axis`, run the fused
    local kernel, merge partials with an exact int64 `psum` over ICI.
    Returns a jitted fn over [n_dev * rows] arrays."""

    def step(qty, price, disc, tax, rf, ls, ship, row_valid):
        def local(qty, price, disc, tax, rf, ls, ship, rv):
            parts = q1_local_kernel(spec, qty, price, disc, tax, rf, ls, ship, rv)
            return tuple(jax.lax.psum(p, axis) for p in parts)

        sharded = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis),) * 8,
            out_specs=(P(),) * 6,
        )
        return sharded(qty, price, disc, tax, rf, ls, ship, row_valid)

    return jax.jit(step)


def hash_repartition(mesh: Mesh, cap: int | None = None, axis: str = "dp"):
    """The MPP exchange primitive: redistribute rows so that rows with
    equal key land on the same device (key % n_devices ownership), via
    `all_to_all` over the mesh axis (ref: ExchangeSender hash mode,
    cophandler/mpp_exec.go:109-206; TiFlash exchange → ICI collective).

    Takes [n_dev*rows] key + payload lanes; returns per-device buckets
    [n_dev*cap]. `cap` is the per-peer send-buffer size: default (None)
    = local rows, which can never drop; a smaller cap trades memory for a
    nonzero `dropped` count (skew overflow — spill path is host-side).
    Returns a jitted fn → (keys_out, payload_out, valid_out, dropped)."""
    n_dev = mesh.shape[axis]
    fixed_cap = cap

    def step(keys, payload, valid):
        def local(keys, payload, valid):
            keys = keys.reshape(-1)
            payload = payload.reshape(-1)
            valid = valid.reshape(-1)
            rows = keys.shape[0]
            cap = fixed_cap if fixed_cap is not None else rows
            owner = (keys % n_dev).astype(jnp.int32)
            # stable-sort rows by owner so each peer's rows are contiguous
            order = jnp.argsort(jnp.where(valid, owner, n_dev))
            keys_s = keys[order]
            pay_s = payload[order]
            val_s = valid[order]
            own_s = jnp.where(val_s, owner[order], n_dev)
            # per-owner counts and in-bucket offsets
            counts = jax.ops.segment_sum(val_s.astype(jnp.int32), own_s, num_segments=n_dev + 1)[:n_dev]
            starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
            idx = jnp.arange(rows)
            within = idx - starts[jnp.clip(own_s, 0, n_dev - 1)]
            # scatter into [n_dev, cap] send buffers
            buf_k = jnp.zeros((n_dev, cap), dtype=keys.dtype)
            buf_p = jnp.zeros((n_dev, cap), dtype=payload.dtype)
            buf_v = jnp.zeros((n_dev, cap), dtype=bool)
            ok = val_s & (within < cap)
            tgt = (jnp.clip(own_s, 0, n_dev - 1), jnp.clip(within, 0, cap - 1))
            buf_k = buf_k.at[tgt].set(jnp.where(ok, keys_s, 0))
            buf_p = buf_p.at[tgt].set(jnp.where(ok, pay_s, 0))
            buf_v = buf_v.at[tgt].set(ok)
            dropped = jnp.sum(val_s) - jnp.sum(ok)
            # the exchange: axis-wise all_to_all of the per-peer buffers
            rk = jax.lax.all_to_all(buf_k, axis, 0, 0, tiled=True)
            rp = jax.lax.all_to_all(buf_p, axis, 0, 0, tiled=True)
            rv = jax.lax.all_to_all(buf_v, axis, 0, 0, tiled=True)
            return rk.reshape(-1), rp.reshape(-1), rv.reshape(-1), jax.lax.psum(dropped, axis)

        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis), P()),
        )(keys, payload, valid)

    return jax.jit(step)


def build_q1_arrays(n_rows: int, n_shards: int = 1, seed: int = 7):
    """Tiny-shape Q1 inputs: [n_shards * rows_per_shard] padded lanes."""
    from ..models.tpch import gen_lineitem
    from ..mysqltypes.coretime import parse_datetime

    cols = gen_lineitem(n_rows, seed)
    per = -(-n_rows // n_shards)
    total = per * n_shards

    def pad(a, dtype):
        out = np.zeros(total, dtype=dtype)
        out[:n_rows] = a
        return out

    rf_codes = np.searchsorted(np.array(["A", "N", "R"]), cols["l_returnflag"].astype("U"))
    ls_codes = np.searchsorted(np.array(["F", "O"]), cols["l_linestatus"].astype("U"))
    rv = np.zeros(total, dtype=bool)
    rv[:n_rows] = True
    args = (
        pad(cols["l_quantity"], np.int64),
        pad(cols["l_extendedprice"], np.int64),
        pad(cols["l_discount"], np.int64),
        pad(cols["l_tax"], np.int64),
        pad(rf_codes, np.int64),
        pad(ls_codes, np.int64),
        pad(cols["l_shipdate"], np.int64),
        rv,
    )
    spec = Q1Spec(nseg=6, cutoff=int(parse_datetime("1998-09-02")))
    return spec, args
