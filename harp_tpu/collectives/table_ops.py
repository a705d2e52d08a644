"""Table-level collectives — Harp's user-facing collective API, TPU-native.

Reference parity: the instance methods on ``CollectiveMapper``
(core/harp-hadoop/.../CollectiveMapper.java — broadcast:403, reduce:431,
allgather:455, allreduce:479, regroup:505, pull:538, push:573, rotate:606) and the
static classes in ``collective/``. Each op here is a distribution-state transition on
a :class:`harp_tpu.table.Table` (see table.py docstring for the state model) that
lowers to exactly one XLA collective.

These functions run INSIDE an SPMD program (shard_map over the ``workers`` axis) —
use :class:`harp_tpu.session.HarpSession` to enter one. Non-block partition→worker
maps are handled by a static permutation of the partition axis (harp_tpu.partitioner):
permute → block collective → (on gather) inverse-permute, so arbitrary Harp
partitioners cost one local gather, never extra network.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from harp_tpu import combiner as combiner_lib
from harp_tpu import partitioner as partitioner_lib
from harp_tpu.collectives import lax_ops
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.table import Dist, Table
from harp_tpu.telemetry.scopes import scoped


def _perm_apply(data: jax.Array, perm) -> jax.Array:
    import numpy as np

    if perm is None or bool(np.all(np.asarray(perm) == np.arange(len(perm)))):
        return data
    return jnp.take(data, jnp.asarray(perm), axis=0)


@scoped("table.allreduce")
def allreduce(t: Table, axis_name: str = WORKERS, comm=None, residual=None):
    """LOCAL → REPLICATED: combine per-worker contributions partition-wise.

    Reference: AllreduceCollective.allreduce:150 / CollectiveMapper.allreduce:479.

    ``comm``/``residual``: opt-in quantized wire format + error-feedback
    state (collectives/quantize.py); with ``residual`` the return is
    ``(table, residual')``, same contract as :func:`regroup`.
    """
    _expect(t, Dist.LOCAL, "allreduce")
    if residual is not None:
        out, residual = lax_ops.allreduce(t.data, t.combiner, axis_name,
                                          comm=comm, residual=residual)
        return t.with_data(out, Dist.REPLICATED), residual
    out = lax_ops.allreduce(t.data, t.combiner, axis_name, comm=comm)
    return t.with_data(out, Dist.REPLICATED)


@scoped("table.reduce")
def reduce(t: Table, root: int = 0, axis_name: str = WORKERS) -> Table:
    """LOCAL → LOCAL: combined table on ``root``, identity elsewhere
    (ReduceCollective.reduce:150)."""
    _expect(t, Dist.LOCAL, "reduce")
    out = lax_ops.reduce(t.data, root, t.combiner, axis_name)
    return t.with_data(out, Dist.LOCAL)


@scoped("table.broadcast")
def broadcast(t: Table, root: int = 0, axis_name: str = WORKERS) -> Table:
    """LOCAL@root → REPLICATED (BcastCollective.broadcast:338)."""
    out = lax_ops.broadcast(t.data, root, axis_name)
    return t.with_data(out, Dist.REPLICATED)


@scoped("table.regroup")
def regroup(
    t: Table,
    partitioner: Optional[partitioner_lib.Partitioner] = None,
    axis_name: str = WORKERS,
    comm=None,
    residual=None,
):
    """LOCAL → SHARDED: route each partition to its owner, combining contributions.

    Reference: RegroupCollective.regroupCombine:154 (partitioner → P2P dispatch →
    combine-on-arrival). Lowered to reduce_scatter (SUM/AVG) or all_to_all+combine.

    ``comm``/``residual``: opt-in quantized wire format + error-feedback
    state (collectives/quantize.py). With ``residual`` the return is
    ``(table, residual')`` — residuals live in the PRE-permutation partition
    order (t.data's), so the same partitioner must ride every call.
    """
    _expect(t, Dist.LOCAL, "regroup")
    perm = partitioner.permutation() if partitioner is not None else None
    data = _perm_apply(t.data, perm)
    res = _perm_apply(residual, perm) if residual is not None else None
    if res is not None:
        out, res = lax_ops.reduce_scatter(data, t.combiner, axis_name,
                                          comm=comm, residual=res)
        inv = (partitioner.inverse_permutation() if partitioner is not None
               else None)
        return t.with_data(out, Dist.SHARDED), _perm_apply(res, inv)
    out = lax_ops.reduce_scatter(data, t.combiner, axis_name, comm=comm)
    return t.with_data(out, Dist.SHARDED)


@scoped("table.allgather")
def allgather(
    t: Table,
    partitioner: Optional[partitioner_lib.Partitioner] = None,
    axis_name: str = WORKERS,
    comm=None,
    fused: bool = False,
) -> Table:
    """SHARDED → REPLICATED (AllgatherCollective.allgather:147, ring relay).

    ``partitioner`` must match the one used at regroup time so partition-ID order is
    restored after the gather. ``comm``: opt-in quantized wire format
    (stateless — the gathered result stays replicated-consistent).
    ``fused`` (r10): the reference's ring relay as W−1 fused in-kernel DMA
    hops (ops/ring_dma; bitwise ``all_gather``) — the Table-level face of
    the shared ring engine.
    """
    _expect(t, Dist.SHARDED, "allgather")
    full = lax_ops.allgather(t.data, axis_name, comm=comm, fused=fused)
    inv = partitioner.inverse_permutation() if partitioner is not None else None
    full = _perm_apply(full, inv)
    return t.with_data(full, Dist.REPLICATED)


@scoped("table.aggregate")
def aggregate(
    t: Table,
    partitioner: Optional[partitioner_lib.Partitioner] = None,
    axis_name: str = WORKERS,
) -> Table:
    """LOCAL → REPLICATED via regroup+allgather (RegroupCollective.aggregate:268).

    On TPU this is exactly reduce_scatter + all_gather — the bandwidth-optimal
    allreduce decomposition — so ``aggregate`` and ``allreduce`` cost the same; Harp
    exposed both because its TCP implementations differed.
    """
    return allgather(regroup(t, partitioner, axis_name), partitioner, axis_name)


@scoped("table.rotate")
def rotate(t: Table, steps: int = 1, axis_name: str = WORKERS) -> Table:
    """SHARDED → SHARDED: ring-shift ownership by ``steps``
    (LocalGlobalSyncCollective.rotate:710 → ppermute over the ICI ring)."""
    _expect(t, Dist.SHARDED, "rotate")
    return t.with_data(lax_ops.rotate(t.data, steps, axis_name))


@scoped("table.rotate_with_map")
def rotate_with_map(t: Table, mapping: dict, axis_name: str = WORKERS) -> Table:
    """Rotate with an explicit worker→worker map (rotateGlobal:746)."""
    _expect(t, Dist.SHARDED, "rotate")
    return t.with_data(lax_ops.rotate_map(t.data, mapping, axis_name))


@scoped("table.push")
def push(
    local: Table,
    global_table: Table,
    partitioner: Optional[partitioner_lib.Partitioner] = None,
    axis_name: str = WORKERS,
    comm=None,
    residual=None,
):
    """Parameter-server push: combine LOCAL contributions into the persistent
    SHARDED global table (LocalGlobalSyncCollective.push:209).

    ``comm``/``residual``: quantize the regroup's wire format; with
    ``residual`` the return is ``(table, residual')`` (see :func:`regroup`).
    """
    _expect(local, Dist.LOCAL, "push")
    _expect(global_table, Dist.SHARDED, "push(global)")
    if residual is not None:
        delta, residual = regroup(local, partitioner, axis_name, comm=comm,
                                  residual=residual)
        merged = global_table.combiner.fn(global_table.data, delta.data)
        return global_table.with_data(merged), residual
    delta = regroup(local, partitioner, axis_name, comm=comm)
    merged = global_table.combiner.fn(global_table.data, delta.data)
    return global_table.with_data(merged)


@scoped("table.pull")
def pull(
    global_table: Table,
    partitioner: Optional[partitioner_lib.Partitioner] = None,
    axis_name: str = WORKERS,
    comm=None,
    fused: bool = False,
) -> Table:
    """Parameter-server pull: SHARDED global → REPLICATED local copy
    (LocalGlobalSyncCollective.pull:185; the chain-bcast variant :228-295 is an XLA
    scheduling detail here). ``comm``: quantized wire format for the gather;
    ``fused``: the r10 ring-DMA relay (see :func:`allgather`)."""
    return allgather(global_table, partitioner, axis_name, comm=comm,
                     fused=fused)


@scoped("table.gather")
def gather(t: Table, root: int = 0, axis_name: str = WORKERS) -> Table:
    """SHARDED → root holds the full table (Communication.gather:196)."""
    _expect(t, Dist.SHARDED, "gather")
    out = lax_ops.gather(t.data, root, axis_name)
    return t.with_data(out, Dist.LOCAL)


@scoped("table.join")
def join(
    dynamic: Table,
    static: Table,
    partitioner: Optional[partitioner_lib.Partitioner] = None,
    axis_name: str = WORKERS,
) -> Table:
    """Co-locate a dynamic table with a static one (GraphCollective.join:313).

    Harp routed the dynamic table's partitions to whichever worker held the
    matching static partition (vertex tables joining edge tables). Here the
    join is a regroup of the dynamic table; co-location holds ONLY when
    ``partitioner`` is the same one used to shard the static table (a Table
    does not carry its layout, so this contract is the caller's — pass None
    iff the static table uses the default block layout).
    """
    _expect(static, Dist.SHARDED, "join(static)")
    _expect(dynamic, Dist.LOCAL, "join(dynamic)")
    if dynamic.num_partitions != static.num_partitions:
        raise ValueError(
            f"join requires matching partition counts: dynamic has "
            f"{dynamic.num_partitions}, static has {static.num_partitions}")
    return regroup(dynamic, partitioner, axis_name)


@scoped("table.group_by_key")
def group_by_key(
    keys: jax.Array,
    values: jax.Array,
    num_keys: int,
    combiner: combiner_lib.Combiner = combiner_lib.SUM,
    axis_name: str = WORKERS,
) -> jax.Array:
    """GroupByKeyCollective:42 — shuffle KV pairs by key, combining equal keys.

    TPU-native: all_gather the (key, value) records, then a masked segment reduction
    into the dense key space. Returns the combined value per key, REPLICATED.
    ``num_keys`` must be static (the key-space size).
    """
    all_keys = lax_ops.allgather(keys, axis_name)
    all_vals = lax_ops.allgather(values, axis_name)
    if combiner.op in (combiner_lib.Op.SUM, combiner_lib.Op.AVG):
        out = jax.ops.segment_sum(all_vals, all_keys, num_segments=num_keys)
        if combiner.op is combiner_lib.Op.AVG:
            counts = jax.ops.segment_sum(jnp.ones_like(all_keys), all_keys,
                                         num_segments=num_keys)
            out = out / jnp.maximum(counts, 1).astype(out.dtype).reshape(
                (-1,) + (1,) * (out.ndim - 1))
        return out
    if combiner.op is combiner_lib.Op.MAX:
        return jax.ops.segment_max(all_vals, all_keys, num_segments=num_keys)
    if combiner.op is combiner_lib.Op.MIN:
        return jax.ops.segment_min(all_vals, all_keys, num_segments=num_keys)
    raise ValueError(f"group_by_key unsupported for {combiner.op}")


def default_route_capacity(n: int, num_workers: int) -> int:
    """Default per-destination bucket size: 2x a balanced share."""
    return max(1, 2 * -(-n // num_workers))


@scoped("table.bucket_route")
def bucket_route(dest: jax.Array, capacity: int, payloads,
                 valid: Optional[jax.Array] = None,
                 axis_name: str = WORKERS):
    """Fixed-capacity owner routing — the shared shuffle core.

    Routes each record (one row of every array in ``payloads``) to worker
    ``dest[i]`` through one ``all_to_all`` of static (W, capacity) buckets.
    ``valid=False`` rows and out-of-range destinations (``dest < 0`` or
    ``dest >= W``) are excluded without consuming capacity. Returns
    ``(routed, recv_mask, overflow, routing)``:
    ``routed`` mirrors ``payloads`` with shapes (W, capacity, ...);
    ``recv_mask`` marks filled slots; ``overflow`` is the psum'd count of
    VALID records dropped for capacity; ``routing`` feeds
    :func:`route_back`."""
    w = jax.lax.axis_size(axis_name)
    n = dest.shape[0]
    # invalid records (valid=False or negative dest) route to a virtual
    # "drop" destination w so they never consume a real bucket's capacity;
    # dest >= w is likewise dropped by the ok mask below
    keep = dest >= 0
    if valid is not None:
        keep = keep & valid
    dest = jnp.where(keep, dest, w)
    order = jnp.argsort(dest, stable=True)
    d_s = dest[order]
    counts = jnp.bincount(d_s, length=w + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n) - starts[d_s]
    ok = (pos < capacity) & (d_s < w)
    d_c = jnp.minimum(d_s, w - 1)
    pos_c = jnp.minimum(pos, capacity - 1)
    routed = []
    for p in payloads:
        p_s = p[order]
        okf = ok.astype(p_s.dtype).reshape((n,) + (1,) * (p_s.ndim - 1))
        # valid positions are unique → masked scatter-add == set; excluded
        # rows clamp to the last slot but add zeros
        buf = jnp.zeros((w, capacity) + p_s.shape[1:], p_s.dtype
                        ).at[d_c, pos_c].add(p_s * okf)
        routed.append(jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                         concat_axis=0))
    buf_m = jnp.zeros((w, capacity), jnp.float32).at[d_c, pos_c].add(
        ok.astype(jnp.float32))
    recv_mask = jax.lax.all_to_all(buf_m, axis_name, split_axis=0,
                                   concat_axis=0)
    overflow = jax.lax.psum(jnp.sum((~ok) & (d_s < w)), axis_name)
    routing = (order, d_c, pos_c, ok, n)
    return routed, recv_mask, overflow, routing


@scoped("table.route_back")
def route_back(answers, routing, axis_name: str = WORKERS):
    """Return per-slot answers (W, capacity, ...) to the senders, restoring
    the original record order. Second output marks records whose answer
    actually made the round trip (False for capacity-dropped records)."""
    back = jax.lax.all_to_all(answers, axis_name, split_axis=0, concat_axis=0)
    order, d_c, pos_c, ok, n = routing
    picked = back[d_c, pos_c]
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n))
    return picked[inv], ok[inv]


@scoped("table.group_by_key_sharded")
def group_by_key_sharded(
    keys: jax.Array,
    values: jax.Array,
    num_keys: int,
    combiner: combiner_lib.Combiner = combiner_lib.SUM,
    capacity: int = 0,
    replicate_result: bool = True,
    axis_name: str = WORKERS,
) -> Tuple[jax.Array, jax.Array]:
    """Owner-partitioned KV shuffle — the scalable GroupByKeyCollective:42.

    Unlike :func:`group_by_key` (which all_gathers every record to every
    worker — O(N·W) memory), records are routed to their key's owner
    (``key // ceil(num_keys/W)``) through ONE ``all_to_all`` of fixed-capacity
    per-destination buckets, then segment-combined locally: per-worker
    footprint is O(N/W · capacity-slack + num_keys/W), matching the
    reference's point-to-point regroup dispatch.

    ``capacity`` is the per-destination bucket size (default ``2·ceil(n/W)``
    — 2× a balanced share). Records beyond a bucket's capacity are DROPPED
    and counted: the second return value is the global overflow count
    (callers must check it — shapes are static under jit, so overflow cannot
    raise device-side). Returns the combined values REPLICATED over workers
    (``replicate_result=False`` keeps only this worker's (ceil(num_keys/W),
    ...) key block).
    """
    w = jax.lax.axis_size(axis_name)
    kpw = -(-num_keys // w)
    n = keys.shape[0]
    cap = capacity or default_route_capacity(n, w)
    dest = jnp.minimum(keys // kpw, w - 1)
    (rk, rv), rm, overflow, _ = bucket_route(dest, cap, (keys, values),
                                             axis_name=axis_name)
    wid = jax.lax.axis_index(axis_name)
    lk = (rk - wid * kpw).reshape(-1)
    lk = jnp.where(rm.reshape(-1) > 0, lk, kpw)     # invalid → drop segment
    rv = rv.reshape((-1,) + rv.shape[2:])
    rm_f = rm.reshape(-1).astype(rv.dtype).reshape(
        (-1,) + (1,) * (rv.ndim - 1))
    # invalid slots are already excluded: their segment id is redirected to
    # the kpw overflow row, which the [:kpw] slice drops
    if combiner.op in (combiner_lib.Op.SUM, combiner_lib.Op.AVG):
        out = jax.ops.segment_sum(rv, lk, num_segments=kpw + 1)[:kpw]
        if combiner.op is combiner_lib.Op.AVG:
            cnt = jax.ops.segment_sum(rm.reshape(-1), lk,
                                      num_segments=kpw + 1)[:kpw]
            out = out / jnp.maximum(cnt, 1.0).astype(out.dtype).reshape(
                (-1,) + (1,) * (out.ndim - 1))
    elif combiner.op in (combiner_lib.Op.MAX, combiner_lib.Op.MIN):
        seg = (jax.ops.segment_max if combiner.op is combiner_lib.Op.MAX
               else jax.ops.segment_min)
        out = seg(rv, lk, num_segments=kpw + 1)[:kpw]
    else:
        raise ValueError(f"group_by_key_sharded unsupported for {combiner.op}")
    if replicate_result:
        out = lax_ops.allgather(out, axis_name)[:num_keys]
    return out, overflow


def _expect(t: Table, dist: Dist, op: str) -> None:
    if t.dist is not dist:
        raise ValueError(
            f"{op} expects a {dist.value} table, got {t.dist.value} "
            f"(table {t.name!r}); see harp_tpu.table state model"
        )
