"""Sharded sketch kernels: one logical object spread across the mesh.

This is the capability jump over the reference (SURVEY.md §5.7): Redis
pins any single key's value to ONE shard; here a single BloomFilter's bit
plane (or an HLL bank's tenant axis) is split across every chip on the
`shard` mesh axis, and membership probes resolve with one `psum` over ICI.

Kernel scheme (shard_map over mesh axes (dp, shard)):
  * state (T, m): each shard holds columns [s*m_loc, (s+1)*m_loc).
  * op batches: split over dp (each dp group handles its slice of ops,
    state is replicated across dp).
  * contains: each shard gathers its in-range probes, absent probes
    contribute 0, `psum` over `shard` reassembles every probe's bit (exactly
    one shard owns each probe) -> AND over k locally.
  * add: each shard scatters only its in-range probes — no communication at
    all; newly-added reporting needs the same psum as contains.
  * dp axis: results stay dp-sharded (P(dp)) — no cross-dp traffic.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from redisson_tpu.parallel.mesh import DP_AXIS, SHARD_AXIS
from redisson_tpu.ops import hll as hll_ops
from redisson_tpu.utils import hashing as H


# XLA:TPU (jax 0.9.0 / libtpu 0.0.34, v5e 2x2) MISCOMPUTES max/min
# all-reduces over UNSIGNED 8- and 16-bit integers, silently: 2,122 of
# 16,384 random uint8 lanes came back wrong from a pmax over `dp`; int8,
# int32 and every psum were exact (chip run, PR 21).  The planes here are
# uint8 with values below 128 — bits are 0/1, HLL ranks at most 33 — so the
# collective rides int8, same width on the wire, and converts back.


def _pmax_u8(x, axis):
    return jax.lax.pmax(x.astype(jnp.int8), axis).astype(jnp.uint8)


def _pmin_u8(x, axis):
    return jax.lax.pmin(x.astype(jnp.int8), axis).astype(jnp.uint8)


def _local_probe_gather(bits_local, tenant, idx_global, m_local):
    """Per-shard: value of each global probe if locally owned, else 0."""
    shard = jax.lax.axis_index(SHARD_AXIS)
    local = idx_global - shard * m_local
    in_range = (local >= 0) & (local < m_local)
    safe = jnp.clip(local, 0, m_local - 1)
    got = bits_local[tenant[:, None], safe]
    return jnp.where(in_range, got, 0).astype(jnp.uint8), in_range, safe


def make_sharded_bloom_kernels(
    mesh: Mesh, k: int, m: int, n_tenants: int, width: int = 0
):
    """Build (add, contains) jitted over the mesh for a (n_tenants, width)
    plane whose HASH DOMAIN is m (probes index [0, m)).

    width >= m is the stored plane's column count and must divide evenly by
    the shard-axis size; the pad columns [m, width) are never addressed, so
    the same logical filter can re-layout onto a mesh whose shard count does
    not divide m (live resharding, SURVEY §7.3-4 — the slot-migration analog
    of cluster/ClusterConnectionManager.java:358-450 done as array
    re-layout).
    """
    n_shard = mesh.shape[SHARD_AXIS]
    width = width or m
    if width % n_shard != 0:
        raise ValueError(f"width={width} must be divisible by shard axis {n_shard}")
    if width < m:
        raise ValueError(f"width={width} cannot be below the hash domain m={m}")
    m_local = width // n_shard

    state_spec = P(None, SHARD_AXIS)
    ops_spec = P(DP_AXIS)

    def contains_local(bits_local, tenant, lo, hi, n_valid):
        h1, h2 = H.hash_u64_pair(lo, hi, jnp)
        idx = H.bloom_indexes(h1, h2, k, m, jnp)
        got, _, _ = _local_probe_gather(bits_local, tenant, idx, m_local)
        got = jax.lax.psum(got, SHARD_AXIS)  # exactly one shard owns each probe
        found = jnp.all(got > 0, axis=-1)
        dp_idx = jax.lax.axis_index(DP_AXIS)
        base = dp_idx * lo.shape[0]
        valid = (jnp.arange(lo.shape[0], dtype=jnp.int32) + base) < n_valid
        return found & valid

    def add_local(bits_local, tenant, lo, hi, n_valid):
        h1, h2 = H.hash_u64_pair(lo, hi, jnp)
        idx = H.bloom_indexes(h1, h2, k, m, jnp)
        got, in_range, safe = _local_probe_gather(bits_local, tenant, idx, m_local)
        pre = jax.lax.psum(got, SHARD_AXIS)
        dp_idx = jax.lax.axis_index(DP_AXIS)
        base = dp_idx * lo.shape[0]
        valid = (jnp.arange(lo.shape[0], dtype=jnp.int32) + base) < n_valid
        newly = jnp.any(pre == 0, axis=-1) & valid
        # scatter only locally-owned, valid probes; others -> dropped row
        trow = jnp.where(in_range & valid[:, None], tenant[:, None], n_tenants)
        bits_local = bits_local.at[trow, safe].set(jnp.uint8(1), mode="drop")
        # dp groups each scattered their own ops into their dp-replica of the
        # plane; max-combine across dp so every replica sees every write
        bits_local = _pmax_u8(bits_local, DP_AXIS)
        return bits_local, newly

    contains = jax.jit(
        jax.shard_map(
            contains_local,
            mesh=mesh,
            in_specs=(state_spec, ops_spec, ops_spec, ops_spec, P()),
            out_specs=ops_spec,
        )
    )
    add = jax.jit(
        jax.shard_map(
            add_local,
            mesh=mesh,
            in_specs=(state_spec, ops_spec, ops_spec, ops_spec, P()),
            out_specs=(state_spec, ops_spec),
        ),
        donate_argnums=(0,),
    )
    return add, contains


def make_sharded_hll_kernels(mesh: Mesh, p: int, n_rows: int):
    """(n_rows, m_regs) HLL bank with the TENANT axis sharded (each shard
    owns a tenant range — the expert-parallel analog: counters are
    independent, so adds route to the owning shard with no collective;
    estimates are local reduces gathered at the end).  n_rows is the stored
    plane's row count (logical tenants padded up to a shard multiple); pad
    rows are never addressed, so the bank can re-layout onto a mesh with a
    different shard count (live resharding)."""
    n_shard = mesh.shape[SHARD_AXIS]
    if n_rows % n_shard != 0:
        raise ValueError(f"rows={n_rows} must divide by shard axis {n_shard}")
    t_local = n_rows // n_shard
    m = hll_ops.m_of(p)

    state_spec = P(SHARD_AXIS, None)
    ops_spec = P(DP_AXIS)

    def add_local(regs_local, tenant, lo, hi, n_valid):
        h1, h2 = H.hash_u64_pair(lo, hi, jnp)
        idx, rho = hll_ops.idx_rho(h1, h2, p)
        shard = jax.lax.axis_index(SHARD_AXIS)
        local_t = tenant - shard * t_local
        dp_idx = jax.lax.axis_index(DP_AXIS)
        base = dp_idx * lo.shape[0]
        valid = (jnp.arange(lo.shape[0], dtype=jnp.int32) + base) < n_valid
        owned = (local_t >= 0) & (local_t < t_local) & valid
        trow = jnp.where(owned, local_t, t_local)
        regs_local = regs_local.at[trow, idx].max(rho, mode="drop")
        regs_local = _pmax_u8(regs_local, DP_AXIS)
        return regs_local

    def estimate_local(regs_local):
        return hll_ops.estimate(regs_local)

    add = jax.jit(
        jax.shard_map(
            add_local,
            mesh=mesh,
            in_specs=(state_spec, ops_spec, ops_spec, ops_spec, P()),
            out_specs=state_spec,
        ),
        donate_argnums=(0,),
    )
    estimate = jax.jit(
        jax.shard_map(
            estimate_local, mesh=mesh, in_specs=(state_spec,), out_specs=P(SHARD_AXIS)
        )
    )
    return add, estimate


def make_sharded_bitset_kernels(mesh: Mesh, m: int, width: int = 0):
    """(set, get, cardinality) for a single (m,) bit plane column-sharded
    over the `shard` axis — ONE logical RBitSet wider than any one chip's
    HBM (SURVEY.md §5.7: the one-key-one-shard constraint removed).

    Scheme mirrors the bloom kernels: each shard owns bits
    [s*m_loc, (s+1)*m_loc); set/get batches split over dp; gathers psum over
    `shard` (exactly one shard owns each index), scatters touch only owned
    indexes then pmax-combine across dp replicas; cardinality is a local
    popcount + psum.  width >= m pads the stored plane to a shard multiple
    (pad bits stay zero; cardinality is exact) for live resharding."""
    n_shard = mesh.shape[SHARD_AXIS]
    width = width or m
    if width % n_shard != 0:
        raise ValueError(f"width={width} must be divisible by shard axis {n_shard}")
    if width < m:
        raise ValueError(f"width={width} cannot be below logical size m={m}")
    m_local = width // n_shard

    state_spec = P(SHARD_AXIS)
    ops_spec = P(DP_AXIS)

    def _owned(idx):
        shard = jax.lax.axis_index(SHARD_AXIS)
        local = idx - shard * m_local
        in_range = (local >= 0) & (local < m_local)
        return jnp.clip(local, 0, m_local - 1), in_range

    def _valid(idx, n_valid):
        dp_idx = jax.lax.axis_index(DP_AXIS)
        base = dp_idx * idx.shape[0]
        return (jnp.arange(idx.shape[0], dtype=jnp.int32) + base) < n_valid

    def get_local(bits_local, idx, n_valid):
        safe, in_range = _owned(idx)
        got = jnp.where(in_range, bits_local[safe], 0).astype(jnp.uint8)
        return (jax.lax.psum(got, SHARD_AXIS) > 0) & _valid(idx, n_valid)

    def make_set(setting: bool):
        # the set/clear direction is known host-side, so it is a STATIC
        # kernel parameter: each variant emits exactly ONE dp collective
        # (pmax converges sets, pmin converges clears) instead of paying
        # both full-plane all-reduces on every write
        def set_local(bits_local, idx, n_valid):
            safe, in_range = _owned(idx)
            old = jnp.where(in_range, bits_local[safe], 0).astype(jnp.uint8)
            old = jax.lax.psum(old, SHARD_AXIS) > 0
            valid = _valid(idx, n_valid)
            target = jnp.where(in_range & valid, safe, m_local)  # pad -> dropped
            bits_local = bits_local.at[target].set(
                jnp.uint8(1 if setting else 0), mode="drop"
            )
            combined = (
                _pmax_u8(bits_local, DP_AXIS)
                if setting
                else _pmin_u8(bits_local, DP_AXIS)
            )
            return combined, old & valid

        return jax.jit(
            jax.shard_map(
                set_local, mesh=mesh,
                in_specs=(state_spec, ops_spec, P()),
                out_specs=(state_spec, ops_spec),
            ),
            donate_argnums=(0,),
        )

    def card_local(bits_local):
        # int32 accumulator: x64 is disabled in this runtime and a per-shard
        # popcount beyond 2^31 set bits (>2 Gbit set on ONE shard) is past
        # any plane this handle serves
        return jax.lax.psum(jnp.sum(bits_local, dtype=jnp.int32), SHARD_AXIS)

    get = jax.jit(
        jax.shard_map(
            get_local, mesh=mesh,
            in_specs=(state_spec, ops_spec, P()),
            out_specs=ops_spec,
        )
    )
    card = jax.jit(
        jax.shard_map(card_local, mesh=mesh, in_specs=(state_spec,), out_specs=P())
    )
    return (make_set(True), make_set(False)), get, card
