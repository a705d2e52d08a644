"""Array-level collective primitives, usable inside shard_map over the worker axis.

Reference parity: Harp's eight collectives in ``collective/`` (SURVEY §2.1). The
reference hand-implements comm algorithms over TCP — chain & MST broadcast
(BcastCollective.broadcast:338), recursive halving/doubling allreduce
(AllreduceCollective.allreduce:150-291), ring allgather (AllgatherCollective:155-213),
point-to-point regroup (RegroupCollective.regroupCombine:154), ring rotate
(LocalGlobalSyncCollective.rotate:710). On TPU the *algorithm choice* belongs to XLA:
each op here is a single named collective and XLA picks the ICI/DCN schedule
(bidirectional rings, etc.). What we keep from Harp is the vocabulary and semantics.

All functions take ``axis_name`` (default "workers") and must be called inside a
``shard_map``/``pmap`` context binding that axis.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from harp_tpu import combiner as combiner_lib
from harp_tpu.collectives import quantize
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.telemetry.scopes import scoped


def worker_id(axis_name: str = WORKERS) -> jax.Array:
    """This worker's ID inside the SPMD program (Harp: Workers.getSelfID)."""
    return jax.lax.axis_index(axis_name)


def num_workers(axis_name: str = WORKERS) -> int:
    return jax.lax.axis_size(axis_name)


@scoped("lax.barrier")
def barrier(axis_name: str = WORKERS) -> None:
    """Reference: Communication.barrier:61 (master counts workers then replies).

    Under SPMD a barrier is implicit — every collective synchronizes the axis. This
    exists for API parity and for forcing ordering in timing code; it lowers to a
    1-element psum that XLA cannot elide across.
    """
    jax.lax.psum(jnp.ones((), jnp.int32), axis_name)


@scoped("lax.allreduce")
def allreduce(
    x: jax.Array,
    combiner: combiner_lib.Combiner = combiner_lib.SUM,
    axis_name: str = WORKERS,
    comm: Optional[quantize.CommConfig] = None,
    residual: Optional[jax.Array] = None,
):
    """All workers end with the combined value.

    Reference: AllreduceCollective.allreduce:150 (recursive halving/doubling).

    ``comm`` (opt-in, quantize.CommConfig): int8/bf16 wire format via the
    two-stage quantized decomposition — dequantize-after-transport, f32
    accumulation (collectives/quantize.py). When ``residual`` is passed
    (error-feedback state shaped like x) the return is ``(out, residual')``
    — also on the f32 path, so call sites stay uniform."""
    if comm is not None and comm.active:
        return quantize.allreduce_q(x, combiner, axis_name, comm, residual)
    out = combiner.psum_like(x, axis_name)
    return (out, residual) if residual is not None else out


@scoped("lax.reduce")
def reduce(
    x: jax.Array,
    root: int = 0,
    combiner: combiner_lib.Combiner = combiner_lib.SUM,
    axis_name: str = WORKERS,
) -> jax.Array:
    """Combined value lands on ``root``; other workers get the combiner identity.

    Reference: ReduceCollective.reduce:150. On ICI a rooted reduce costs the same as
    allreduce (the fabric is symmetric), so this is allreduce + mask — the mask keeps
    Harp's semantics observable (non-roots don't see the result).
    """
    full = combiner.psum_like(x, axis_name)
    mask = jax.lax.axis_index(axis_name) == root
    return jnp.where(mask, full, jnp.full_like(full, combiner.identity))


@scoped("lax.broadcast")
def broadcast(x: jax.Array, root: int = 0, axis_name: str = WORKERS) -> jax.Array:
    """Every worker ends with ``root``'s value.

    Reference: BcastCollective.broadcast:338 (chain or MST over TCP). Lowered as a
    masked psum, which XLA turns into an ICI broadcast tree.
    """
    mask = jax.lax.axis_index(axis_name) == root
    return jax.lax.psum(jnp.where(mask, x, jnp.zeros_like(x)), axis_name)


@scoped("lax.allgather")
def allgather(x: jax.Array, axis_name: str = WORKERS, tiled: bool = True,
              comm: Optional[quantize.CommConfig] = None,
              fused: bool = False) -> jax.Array:
    """Concatenate every worker's block along axis 0 (ring allgather).

    Reference: AllgatherCollective.allgather:147 (send-to-next ring relay).
    ``comm``: opt-in quantized wire format (stateless — every worker decodes
    the same payload, so the gathered result stays replicated-consistent).

    ``fused`` (r10): run the reference's ring relay LITERALLY as W−1 fused
    in-kernel DMA hops (ops/ring_dma.ring_allgather — bitwise
    ``all_gather``, no per-hop staging copies; off TPU the engine's tagged
    fallback keeps the jaxpr budget honest). A quantized wire takes
    precedence (the codec needs its encode/decode programs around the
    transport)."""
    if comm is not None and comm.active:
        return quantize.allgather_q(x, axis_name, comm, tiled=tiled)
    if fused:
        from harp_tpu.ops import ring_dma  # local: ring_dma imports lax_ops

        if tiled:
            return ring_dma.ring_allgather(x, axis_name)
        return ring_dma.ring_allgather(x[None], axis_name)
    return jax.lax.all_gather(x, axis_name, tiled=tiled)


@scoped("lax.gather")
def gather(x: jax.Array, root: int = 0, axis_name: str = WORKERS,
           tiled: bool = True) -> jax.Array:
    """Root ends with all blocks; others get zeros (Communication.gather:196)."""
    full = jax.lax.all_gather(x, axis_name, tiled=tiled)
    mask = jax.lax.axis_index(axis_name) == root
    return jnp.where(mask, full, jnp.zeros_like(full))


@scoped("lax.reduce_scatter")
def reduce_scatter(
    x: jax.Array,
    combiner: combiner_lib.Combiner = combiner_lib.SUM,
    axis_name: str = WORKERS,
    comm: Optional[quantize.CommConfig] = None,
    residual: Optional[jax.Array] = None,
):
    """Combine per-worker contributions and scatter blocks: worker w gets the
    combined block w of the partition axis.

    This is Harp's ``regroup`` with the block partitioner
    (RegroupCollective.regroupCombine:154: partitioner → P2P dispatch → combine on
    arrival). SUM/AVG lower to ``psum_scatter``; other algebras lower to
    ``all_to_all`` + a local combine (XLA has no reduce_scatter for max/min).

    ``comm``/``residual``: opt-in quantized wire format + error-feedback
    state, same contract as :func:`allreduce` (SUM/AVG only).
    """
    if comm is not None and comm.active:
        return quantize.reduce_scatter_q(x, combiner, axis_name, comm,
                                         residual)
    if residual is not None:
        out = reduce_scatter(x, combiner, axis_name)
        return out, residual
    n = jax.lax.axis_size(axis_name)
    if combiner.op in (combiner_lib.Op.SUM, combiner_lib.Op.AVG):
        out = jax.lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
        if combiner.op is combiner_lib.Op.AVG:
            out = out / n
        return out
    # General algebra: exchange blocks, then combine the n contributions locally.
    block = x.shape[0] // n
    chunks = x.reshape((n, block) + x.shape[1:])
    # all_to_all: chunk j of worker i -> worker j's slot i.
    exchanged = jax.lax.all_to_all(chunks, axis_name, split_axis=0, concat_axis=0)
    return combiner.tree_combine(exchanged, axis=0)


@scoped("lax.rotate")
def rotate(x: jax.Array, steps: int = 1, axis_name: str = WORKERS,
           comm: Optional[quantize.CommConfig] = None,
           num_chunks: int = 1) -> jax.Array:
    """Ring-shift this worker's block to ``(id + steps) % n`` — i.e. each worker
    receives the block previously held by ``id - steps``.

    Reference: LocalGlobalSyncCollective.rotate:710 (ring or custom rotateMap).
    Lowered to ``ppermute`` which maps 1:1 onto neighbor ICI links.

    ``comm``: opt-in quantized wire format (stateless; rotation loops carry
    error feedback in ``rotation.rotate_scan``'s carry instead).
    ``num_chunks`` > 1 splits the block into that many ppermutes along axis
    0 — DCN-hop pipelining (``rotation.chunks_for_link``): XLA's async
    collective scheduler overlaps in-flight chunks over a slow link, where
    one monolithic permute would serialize behind the first byte.
    """
    if comm is not None and comm.active:
        # chunking composes with quantization at the whole-block level: the
        # encode is one program either way, and a quantized DCN hop is
        # already 2-4x smaller than the chunking threshold assumes
        return quantize.rotate_q(x, steps, axis_name, comm)
    return ring_shift(x, steps, axis_name, num_chunks)


def ring_shift(x: jax.Array, steps: int, axis_name: str = WORKERS,
               num_chunks: int = 1) -> jax.Array:
    """The bare ring ``ppermute`` of :func:`rotate`, under no scope of its
    own: ``rotate`` names it ``lax.rotate``, the rotation loops name it
    ``rotation.hop`` (telemetry/scopes.py)."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + steps) % n) for i in range(n)]
    if num_chunks > 1 and x.ndim and x.shape[0] > 1:
        parts = jnp.array_split(x, min(num_chunks, x.shape[0]), axis=0)
        return jnp.concatenate(
            [jax.lax.ppermute(p, axis_name, perm) for p in parts], axis=0)
    return jax.lax.ppermute(x, axis_name, perm)


@scoped("lax.rotate_map")
def rotate_map(x: jax.Array, mapping: dict, axis_name: str = WORKERS) -> jax.Array:
    """Rotate with an explicit worker→worker map (Harp's rotateMap Int2IntMap,
    LocalGlobalSyncCollective.rotateGlobal:746).

    ``mapping`` must be a bijection over the whole axis: ``ppermute`` sends
    nothing for missing sources and delivers ZEROS to unnamed destinations,
    so a malformed map would silently drop shards — validate loudly instead.
    """
    n = jax.lax.axis_size(axis_name)
    srcs, dsts = set(mapping.keys()), set(mapping.values())
    expect = set(range(n))
    if srcs != expect or dsts != expect:
        missing_src = sorted(expect - srcs)
        missing_dst = sorted(expect - dsts)
        bad = sorted((srcs | dsts) - expect)
        raise ValueError(
            f"rotate_map mapping must be a bijection over all {n} workers: "
            f"sources missing {missing_src}, destinations missing "
            f"{missing_dst}, out-of-range ids {bad} — a partial map would "
            f"silently replace the unnamed workers' shards with zeros")
    perm = sorted(mapping.items())
    return jax.lax.ppermute(x, axis_name, perm)


@scoped("lax.all_to_all")
def all_to_all(x: jax.Array, axis_name: str = WORKERS) -> jax.Array:
    """Block transpose across workers: chunk j of worker i → slot i of worker j.

    The substrate for general regroup and for Ulysses-style sequence parallelism.
    ``x`` has shape (n*block, ...); result has the same shape.
    """
    n = jax.lax.axis_size(axis_name)
    block = x.shape[0] // n
    chunks = x.reshape((n, block) + x.shape[1:])
    out = jax.lax.all_to_all(chunks, axis_name, split_axis=0, concat_axis=0)
    return out.reshape((n * block,) + x.shape[1:])


@scoped("lax.send_recv")
def send_recv(x: jax.Array, pairs: list[tuple[int, int]],
              axis_name: str = WORKERS) -> jax.Array:
    """Point-to-point sends (source, dest) — Harp's DataSender/event substitute.

    Workers not receiving anything get zeros.
    """
    return jax.lax.ppermute(x, axis_name, pairs)
