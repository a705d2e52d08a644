"""Typed key-value tables — the TPU-native keyval/ layer.

Reference parity: ``keyval/`` (2,573 LoC: ``Key2ValKVTable``:88,
``Int2IntKVTable``:63, ``Long2DoubleKVTable``, open-hash partitions with
per-value ``ValCombiner``s) — the substrate for Harp's graph apps and
group-by. The reference's open-addressing hash maps are pointer-chasing
structures a TPU cannot run; the TPU-native equivalent here is a
**sorted dense store with sort-merge updates**:

* A :class:`KVStore` is a fixed-capacity pair of arrays ``(keys, vals)``
  sorted by key, empty slots holding an int-max sentinel. All shapes are
  static — XLA-friendly by construction.
* ``kv_merge`` (the ``add(key, val)``-with-combiner surface) concatenates the
  incoming batch, sorts (XLA lowers to an on-device bitonic sort), combines
  equal-key runs with a segment reduction (the ``ValCombiner``), and
  recompacts. Capacity overflow is COUNTED and returned, never silent.
* ``kv_lookup`` is a vectorized binary search (``searchsorted``) — O(log cap)
  per query with full lane parallelism, replacing per-key hash probes.
* :class:`DistributedKV` shards the key space by ``key mod W`` over the mesh;
  updates and lookups route through one ``all_to_all`` each way (the same
  owner-routing as ``collectives.table_ops.group_by_key_sharded``), combining
  on arrival exactly like the reference's regroup-with-combiner.

Value dtypes follow the arrays you pass — ``int32``/``float32`` stores give
the Int2Int / Int2Double / Long2Double family without a class per type.
KEY SPACE: the 32-bit stores take keys in ``[0, 2^31 - 2]`` — the int32
maximum is reserved as the empty-slot/padding sentinel. For wider keys
(graph vertex ids past int32 — ``Long2DoubleKVTable``), the ``KVStore64`` /
``DistributedKV64`` family carries 64-bit keys as (hi, lo) int32 pairs
(``split_keys64``/``join_keys64``) covering ``[0, 2^62 − 2^31)`` with the
same merge/lookup/overflow contract.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import combiner as combiner_lib
from harp_tpu.collectives.table_ops import (bucket_route,
                                            default_route_capacity,
                                            route_back)
from harp_tpu.parallel.mesh import WORKERS

EMPTY = jnp.iinfo(jnp.int32).max     # sentinel key for empty slots


@dataclasses.dataclass
class KVStore:
    """A fixed-capacity sorted key-value store (one worker's partition)."""

    keys: jax.Array          # (cap,) int32, sorted, EMPTY-padded
    vals: jax.Array          # (cap,) + value shape
    count: jax.Array         # () int32 — live entries

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]


def kv_empty(capacity: int, val_shape: Tuple[int, ...] = (),
             val_dtype=jnp.float32) -> KVStore:
    return KVStore(
        keys=jnp.full((capacity,), EMPTY, jnp.int32),
        vals=jnp.zeros((capacity,) + tuple(val_shape), val_dtype),
        count=jnp.zeros((), jnp.int32),
    )


def _segment_combine(vals, seg_ids, num_segments, combiner):
    if combiner.op in (combiner_lib.Op.SUM, combiner_lib.Op.AVG):
        out = jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments)
        if combiner.op is combiner_lib.Op.AVG:
            cnt = jax.ops.segment_sum(jnp.ones(vals.shape[0], vals.dtype),
                                      seg_ids, num_segments=num_segments)
            shape = (-1,) + (1,) * (vals.ndim - 1)
            out = out / jnp.maximum(cnt, 1).reshape(shape)
        return out
    if combiner.op is combiner_lib.Op.MAX:
        return jax.ops.segment_max(vals, seg_ids, num_segments=num_segments)
    if combiner.op is combiner_lib.Op.MIN:
        return jax.ops.segment_min(vals, seg_ids, num_segments=num_segments)
    if combiner.op is combiner_lib.Op.MULTIPLY:
        return jax.ops.segment_prod(vals, seg_ids, num_segments=num_segments)
    raise ValueError(f"kv combiner unsupported: {combiner.op}")


def kv_merge(store: KVStore, keys: jax.Array, vals: jax.Array,
             combiner: combiner_lib.Combiner = combiner_lib.SUM,
             mask: Optional[jax.Array] = None
             ) -> Tuple[KVStore, jax.Array]:
    """Insert-or-combine a batch of records (Key2ValKVTable.add semantics).

    ``mask`` marks valid incoming records (padding rows are ignored; a key
    equal to the int32-max sentinel is always treated as padding). Returns
    (new store, overflow count) — overflow = live keys beyond capacity after
    the merge; the LARGEST keys are dropped, deterministically.
    """
    cap = store.capacity
    vals = vals.astype(store.vals.dtype)
    if mask is not None:
        in_keys = jnp.where(mask, keys.astype(jnp.int32), EMPTY)
        vals = vals * mask.astype(vals.dtype).reshape(
            (-1,) + (1,) * (vals.ndim - 1))
    else:
        in_keys = keys.astype(jnp.int32)
    all_keys = jnp.concatenate([store.keys, in_keys])
    all_vals = jnp.concatenate([store.vals, vals])
    order = jnp.argsort(all_keys, stable=True)
    k_s = all_keys[order]
    v_s = all_vals[order]
    # equal-key runs → segment ids; EMPTY keys form the final run
    is_new = jnp.concatenate([jnp.ones((1,), bool), k_s[1:] != k_s[:-1]])
    seg = jnp.cumsum(is_new) - 1
    n_total = all_keys.shape[0]
    combined = _segment_combine(v_s, seg, n_total, combiner)
    uniq_keys = jax.ops.segment_min(k_s, seg, num_segments=n_total)
    uniq_keys = jnp.where(jnp.arange(n_total) <= seg[-1], uniq_keys, EMPTY)
    live = jnp.sum((uniq_keys != EMPTY).astype(jnp.int32))
    overflow = jnp.maximum(live - cap, 0)
    return KVStore(keys=uniq_keys[:cap], vals=combined[:cap],
                   count=jnp.minimum(live, cap)), overflow


def kv_lookup(store: KVStore, keys: jax.Array, default=0
              ) -> Tuple[jax.Array, jax.Array]:
    """Vectorized lookup. Returns (values, found-mask); missing keys get
    ``default``."""
    q = keys.astype(jnp.int32)
    idx = jnp.searchsorted(store.keys, q)
    idx = jnp.minimum(idx, store.capacity - 1)
    found = (store.keys[idx] == q) & (q != EMPTY)   # EMPTY never matches
    shape = (-1,) + (1,) * (store.vals.ndim - 1)
    vals = jnp.where(found.reshape(shape), store.vals[idx],
                     jnp.asarray(default, store.vals.dtype))
    return vals, found


# --------------------------------------------------------------------------- #
# Distributed table (key space sharded by key mod W)
# --------------------------------------------------------------------------- #

class DistributedKV:
    """Mesh-sharded typed KV table (the Key2ValKVTable surface, distributed).

    Construct inside or outside an SPMD program with a per-worker
    :class:`KVStore`; ``update``/``lookup`` are SPMD collectives (run them
    inside ``session.spmd``). Ownership: ``key mod W``.
    """

    def __init__(self, store: KVStore, axis_name: str = WORKERS):
        self.store = store
        self.axis_name = axis_name

    def update(self, keys, vals, combiner=combiner_lib.SUM, route_cap: int = 0,
               mask=None, dest=None):
        """Route records to their owners and combine into the local stores.
        Returns (new DistributedKV, route_overflow, store_overflow). Masked
        (padding) records are excluded without consuming route capacity.
        ``dest`` (optional, (n,) int32 in [0, W)) overrides the ``key mod
        W`` owner per record — the seam live REBALANCING uses: a store
        whose shards were moved off a straggler routes by its explicit
        owner map instead of the modulo (serve.endpoints.TopKEndpoint
        .rebalance). Same collectives either way."""
        w = jax.lax.axis_size(self.axis_name)
        n = keys.shape[0]
        cap = route_cap or default_route_capacity(n, w)
        k = keys.astype(jnp.int32)
        valid_in = (k != EMPTY) if mask is None else (mask & (k != EMPTY))
        (rk, rv), rm, ovf, _ = bucket_route(
            k % w if dest is None else dest, cap,
            (jnp.where(valid_in, k, EMPTY), vals),
            valid=valid_in, axis_name=self.axis_name)
        flat_k = rk.reshape(-1)
        flat_v = rv.reshape((-1,) + rv.shape[2:])
        valid = (rm.reshape(-1) > 0) & (flat_k != EMPTY)
        store, s_ovf = kv_merge(self.store, flat_k, flat_v, combiner,
                                mask=valid)
        return DistributedKV(store, self.axis_name), ovf, \
            jax.lax.psum(s_ovf, self.axis_name)

    def lookup(self, keys, default=0, route_cap: int = 0, mask=None,
               dest=None):
        """Distributed get: route queries to owners, answer, route back (one
        all_to_all each way; the found flag rides with the values). Returns
        (values, found) in the original query order; capacity-dropped or
        padding queries (``mask=False`` or the sentinel key) come back as
        (default, False) without consuming route capacity. ``dest``: see
        :meth:`update` — explicit per-query owners for rebalanced stores
        (identical collective counts/kinds, so the serve dispatch budget
        pins hold for both routings)."""
        w = jax.lax.axis_size(self.axis_name)
        n = keys.shape[0]
        cap = route_cap or default_route_capacity(n, w)
        k = keys.astype(jnp.int32)
        valid_q = (k != EMPTY) if mask is None else (mask & (k != EMPTY))
        (rk,), rm, _, routing = bucket_route(k % w if dest is None else dest,
                                             cap, (k,), valid=valid_q,
                                             axis_name=self.axis_name)
        q = jnp.where(rm > 0, rk, EMPTY).reshape(-1)
        vals, found = kv_lookup(self.store, q, default)
        vshape = self.store.vals.shape[1:]
        vdtype = self.store.vals.dtype
        if jnp.issubdtype(vdtype, jnp.floating):
            # pack values + found flag into ONE return all_to_all
            flat = vals.reshape(w, cap, -1).astype(jnp.float32)
            packed = jnp.concatenate(
                [flat, found.reshape(w, cap, 1).astype(jnp.float32)], axis=-1)
            back, ok = route_back(packed, routing, self.axis_name)
            back_f = (back[:, -1] > 0.5) & ok
            back_v = back[:, :-1].reshape((n,) + vshape).astype(vdtype)
        elif vdtype == jnp.int8:
            # int8 rows (the quantized serving payload, ISSUE 17): the
            # found flag packs as one extra int8 column, so the whole
            # answer rides ONE int8 route_back — the same collective count
            # as the f32 pack at roughly a quarter of the bytes (the
            # serve_topk_mf_int8 budget row pins exactly this)
            flat = vals.reshape(w, cap, -1)
            packed = jnp.concatenate(
                [flat, found.reshape(w, cap, 1).astype(jnp.int8)], axis=-1)
            back, ok = route_back(packed, routing, self.axis_name)
            back_f = (back[:, -1] > 0) & ok
            back_v = back[:, :-1].reshape((n,) + vshape)
        else:
            # wider integer values would lose precision through an f32
            # pack — return values and flags in separate trips
            back_v, ok = route_back(vals.reshape((w, cap) + vshape),
                                    routing, self.axis_name)
            back_f0, _ = route_back(found.reshape(w, cap), routing,
                                    self.axis_name)
            back_f = back_f0 & ok
        okv = back_f.reshape((-1,) + (1,) * len(vshape)) if vshape else back_f
        return jnp.where(okv, back_v,
                         jnp.asarray(default, back_v.dtype)), back_f


# --------------------------------------------------------------------------- #
# 64-bit key space (Long2DoubleKVTable parity)
# --------------------------------------------------------------------------- #
#
# JAX runs with 32-bit index types on TPU (x64 disabled), so 64-bit keys are
# carried as (hi, lo) int32 PAIRS: key = hi * 2^31 + lo with hi, lo in
# [0, 2^31). That covers nonnegative keys < 2^62 — graph vertex ids beyond
# int32 (keyval/Long2DoubleKVTable.java). Ordering is lexicographic (hi, lo);
# the (EMPTY, EMPTY) pair is the empty-slot sentinel. The merge is the same
# sort+segment-combine as the 32-bit store; the lookup is an explicit
# vectorized binary search over the pair ordering (log2(cap) steps, all
# queries in parallel) since searchsorted has no composite-key form.

_LO_BITS = 31
_LO_MASK = (1 << _LO_BITS) - 1


_KEY64_MAX = (jnp.iinfo(jnp.int32).max << _LO_BITS)  # hi must stay < EMPTY


def split_keys64(keys) -> Tuple[np.ndarray, np.ndarray]:
    """Host helper: int64 keys (nonneg, < 2^62 − 2^31) → (hi, lo) int32
    arrays. The upper bound keeps hi below the EMPTY sentinel."""
    k = np.asarray(keys, np.int64)
    if len(k) and (k.min() < 0 or k.max() >= _KEY64_MAX):
        raise ValueError(f"64-bit keys must be in [0, {_KEY64_MAX})")
    return ((k >> _LO_BITS).astype(np.int32),
            (k & _LO_MASK).astype(np.int32))


def join_keys64(hi, lo) -> np.ndarray:
    """Host helper: (hi, lo) int32 arrays → int64 keys."""
    return (np.asarray(hi, np.int64) << _LO_BITS) | np.asarray(lo, np.int64)


@dataclasses.dataclass
class KVStore64:
    """Fixed-capacity sorted store over the (hi, lo) 64-bit key space."""

    hi: jax.Array            # (cap,) int32, (hi, lo) lexicographically sorted
    lo: jax.Array            # (cap,) int32
    vals: jax.Array          # (cap,) + value shape
    count: jax.Array         # () int32

    @property
    def capacity(self) -> int:
        return self.hi.shape[0]


def kv64_empty(capacity: int, val_shape: Tuple[int, ...] = (),
               val_dtype=jnp.float32) -> KVStore64:
    return KVStore64(
        hi=jnp.full((capacity,), EMPTY, jnp.int32),
        lo=jnp.full((capacity,), EMPTY, jnp.int32),
        vals=jnp.zeros((capacity,) + tuple(val_shape), val_dtype),
        count=jnp.zeros((), jnp.int32),
    )


def _pair_less(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def kv64_merge(store: KVStore64, hi: jax.Array, lo: jax.Array,
               vals: jax.Array,
               combiner: combiner_lib.Combiner = combiner_lib.SUM,
               mask: Optional[jax.Array] = None
               ) -> Tuple[KVStore64, jax.Array]:
    """64-bit kv_merge: identical contract, lexicographic (hi, lo) order.
    Padding = mask False or hi == EMPTY. Overflow drops the LARGEST keys."""
    cap = store.capacity
    vals = vals.astype(store.vals.dtype)
    in_hi = hi.astype(jnp.int32)
    in_lo = lo.astype(jnp.int32)
    pad = (in_hi == EMPTY) if mask is None else ~mask | (in_hi == EMPTY)
    in_hi = jnp.where(pad, EMPTY, in_hi)
    in_lo = jnp.where(pad, EMPTY, in_lo)
    vals = vals * (~pad).astype(vals.dtype).reshape(
        (-1,) + (1,) * (vals.ndim - 1))
    all_hi = jnp.concatenate([store.hi, in_hi])
    all_lo = jnp.concatenate([store.lo, in_lo])
    all_vals = jnp.concatenate([store.vals, vals])
    order = jnp.lexsort((all_lo, all_hi))        # hi primary, lo secondary
    h_s, l_s, v_s = all_hi[order], all_lo[order], all_vals[order]
    is_new = jnp.concatenate([jnp.ones((1,), bool),
                              (h_s[1:] != h_s[:-1]) | (l_s[1:] != l_s[:-1])])
    seg = jnp.cumsum(is_new) - 1
    n_total = all_hi.shape[0]
    combined = _segment_combine(v_s, seg, n_total, combiner)
    uniq_hi = jax.ops.segment_min(h_s, seg, num_segments=n_total)
    uniq_lo = jax.ops.segment_min(l_s, seg, num_segments=n_total)
    in_range = jnp.arange(n_total) <= seg[-1]
    uniq_hi = jnp.where(in_range, uniq_hi, EMPTY)
    uniq_lo = jnp.where(in_range, uniq_lo, EMPTY)
    live = jnp.sum((uniq_hi != EMPTY).astype(jnp.int32))
    overflow = jnp.maximum(live - cap, 0)
    return KVStore64(hi=uniq_hi[:cap], lo=uniq_lo[:cap], vals=combined[:cap],
                     count=jnp.minimum(live, cap)), overflow


def kv64_lookup(store: KVStore64, hi: jax.Array, lo: jax.Array, default=0
                ) -> Tuple[jax.Array, jax.Array]:
    """Vectorized pair binary search; missing keys get ``default``."""
    q_hi = hi.astype(jnp.int32)
    q_lo = lo.astype(jnp.int32)
    cap = store.capacity
    n = q_hi.shape[0]
    lo_b = jnp.zeros((n,), jnp.int32)
    hi_b = jnp.full((n,), cap, jnp.int32)
    for _ in range(max(cap.bit_length(), 1)):
        mid = (lo_b + hi_b) // 2
        m = jnp.minimum(mid, cap - 1)
        less = _pair_less(store.hi[m], store.lo[m], q_hi, q_lo)
        lo_b = jnp.where(less, mid + 1, lo_b)
        hi_b = jnp.where(less, hi_b, mid)
    idx = jnp.minimum(lo_b, cap - 1)
    found = ((store.hi[idx] == q_hi) & (store.lo[idx] == q_lo)
             & (q_hi != EMPTY))
    shape = (-1,) + (1,) * (store.vals.ndim - 1)
    vals = jnp.where(found.reshape(shape), store.vals[idx],
                     jnp.asarray(default, store.vals.dtype))
    return vals, found


class DistributedKV64:
    """Mesh-sharded 64-bit KV table (Long2DoubleKVTable distributed).

    Ownership: ``key mod W`` computed on the (hi, lo) pair without int64:
    ``((hi % W) * (2^31 % W) + lo % W) % W``."""

    def __init__(self, store: KVStore64, axis_name: str = WORKERS):
        self.store = store
        self.axis_name = axis_name

    def _dest(self, hi, lo, w):
        base = (1 << _LO_BITS) % w
        return ((hi % w) * base + lo % w) % w

    def update(self, hi, lo, vals, combiner=combiner_lib.SUM,
               route_cap: int = 0, mask=None):
        """Route (hi, lo, val) records to owners and combine. Returns
        (new DistributedKV64, route_overflow, store_overflow)."""
        w = jax.lax.axis_size(self.axis_name)
        n = hi.shape[0]
        cap = route_cap or default_route_capacity(n, w)
        h = hi.astype(jnp.int32)
        l = lo.astype(jnp.int32)
        valid_in = (h != EMPTY) if mask is None else (mask & (h != EMPTY))
        (rh, rl, rv), rm, ovf, _ = bucket_route(
            self._dest(h, l, w), cap,
            (jnp.where(valid_in, h, EMPTY), jnp.where(valid_in, l, EMPTY),
             vals),
            valid=valid_in, axis_name=self.axis_name)
        flat_h = rh.reshape(-1)
        flat_l = rl.reshape(-1)
        flat_v = rv.reshape((-1,) + rv.shape[2:])
        valid = (rm.reshape(-1) > 0) & (flat_h != EMPTY)
        store, s_ovf = kv64_merge(self.store, flat_h, flat_l, flat_v,
                                  combiner, mask=valid)
        return DistributedKV64(store, self.axis_name), ovf, \
            jax.lax.psum(s_ovf, self.axis_name)

    def lookup(self, hi, lo, default=0, route_cap: int = 0, mask=None):
        """Distributed get over 64-bit keys; same contract as
        DistributedKV.lookup."""
        w = jax.lax.axis_size(self.axis_name)
        n = hi.shape[0]
        cap = route_cap or default_route_capacity(n, w)
        h = hi.astype(jnp.int32)
        l = lo.astype(jnp.int32)
        valid_q = (h != EMPTY) if mask is None else (mask & (h != EMPTY))
        (rh, rl), rm, _, routing = bucket_route(
            self._dest(h, l, w), cap, (h, l), valid=valid_q,
            axis_name=self.axis_name)
        q_h = jnp.where(rm > 0, rh, EMPTY).reshape(-1)
        q_l = jnp.where(rm > 0, rl, EMPTY).reshape(-1)
        vals, found = kv64_lookup(self.store, q_h, q_l, default)
        vshape = self.store.vals.shape[1:]
        vdtype = self.store.vals.dtype
        if jnp.issubdtype(vdtype, jnp.floating):
            flat = vals.reshape(w, cap, -1).astype(jnp.float32)
            packed = jnp.concatenate(
                [flat, found.reshape(w, cap, 1).astype(jnp.float32)], axis=-1)
            back, ok = route_back(packed, routing, self.axis_name)
            back_f = (back[:, -1] > 0.5) & ok
            back_v = back[:, :-1].reshape((n,) + vshape).astype(vdtype)
        else:
            back_v, ok = route_back(vals.reshape((w, cap) + vshape),
                                    routing, self.axis_name)
            back_f0, _ = route_back(found.reshape(w, cap), routing,
                                    self.axis_name)
            back_f = back_f0 & ok
        okv = back_f.reshape((-1,) + (1,) * len(vshape)) if vshape else back_f
        return jnp.where(okv, back_v,
                         jnp.asarray(default, back_v.dtype)), back_f
