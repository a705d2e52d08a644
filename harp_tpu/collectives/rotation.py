"""Model-rotation pipeline — the TPU-native dymoro.

Reference parity: Harp's **dy**namic **mo**del **ro**tation machinery
(harp-daal-interface dymoro/): ``Rotator`` (dymoro/Rotator.java:30-73) ran rotate ops
on a background StaticScheduler thread so communication overlapped compute, with the
model split into ``numModelSlices`` (=2 in SGD-MF, SGDCollectiveMapper.java:120-223)
— slice k computes while slice k-1 is in flight around the ring.

TPU-native: no background threads. The same schedule is expressed as a ``lax.scan``
whose dataflow makes the overlap visible to XLA: at micro-step t we issue the
``ppermute`` for the just-updated slice and compute on the slice that arrived at
t-1; the permute's result is not consumed until t+1, so XLA's async collective
scheduler overlaps it with the compute — the dymoro pipeline, minus the threads,
scheduled by the compiler onto ICI DMA engines.

The timer-bounded *dynamic* part of dymoro (Scheduler.java:85-160 randomly scheduled
(row, col) blocks until a wall-clock budget expired) is host-driven and
data-dependent — hostile to XLA. Per SURVEY §7 "hard parts", it is reformulated as a
**bounded-staleness fixed block schedule**: a fixed number of randomly-permuted block
updates per rotation hop (seeded, reproducible). Convergence-equivalent, not
step-equivalent; see models/sgd_mf.py.

Wire-format options (this layer owns the hot hops, so both live here):

* ``comm`` (quantize.CommConfig): int8/bf16 quantized hops with
  **error-feedback state carried in the scan carry** — each sender keeps the
  residual its last encode failed to carry and adds it to the next outgoing
  block (EF-ring: the time-average of the fed-back error vanishes). Only
  float32 leaves are quantized; integer/bool leaves ride the wire exact.
* ``link_class`` ("ici" | "dcn", default: the mesh-axis hint,
  ``parallel.mesh.axis_link_class``): a DCN hop splits its payload into
  ~``DCN_CHUNK_BYTES`` ppermute chunks so in-flight pieces pipeline over the
  slow link; an ICI hop stays one monolithic permute (the extra dispatches
  would only cost latency on a fabric that is already one hop wide).
* ``fused_dma`` (r10, ops/ring_dma.py): float-leaf payloads ride the fused
  in-kernel ``make_async_remote_copy`` hop instead of ``ppermute`` — on TPU
  the block moves producer-HBM → remote-HBM with no staging copies; off TPU
  the engine's tagged lax fallback keeps the schedule bitwise-identical and
  the jaxpr budget books the bytes as ``fused_dma``. Precedence: a
  quantized hop (``comm`` active) keeps the quantize path (the wire is
  already 2-4× smaller and needs its encode/decode programs), and a DCN
  hop keeps the chunked ppermute pipeline — ``fused_dma`` engages only on
  plain ICI hops, where it is exact.
* ``ef_state`` (r10): pass a residual tree (:func:`ef_zero`) to carry the
  quantization error-feedback state ACROSS calls — e.g. LDA threads the
  wt-block residual through its epoch scan so an epoch boundary never
  drops the pending error; the call then returns the updated state as an
  extra output.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple, TypeVar

import jax
import jax.numpy as jnp

from harp_tpu.collectives import lax_ops, quantize
from harp_tpu.ops import ring_dma
from harp_tpu.parallel import mesh as mesh_lib
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.telemetry.scopes import scoped

Carry = TypeVar("Carry")
Slice = Any  # pytree of arrays — one model slice's per-worker block

# DCN rotation hops pipeline in ~1 MiB pieces (big enough to amortize
# per-message overhead on a data-center link, small enough that several are
# in flight); capped at 8 chunks so tiny payloads don't shatter.
DCN_CHUNK_BYTES = 1 << 20
MAX_DCN_CHUNKS = 8


def chunks_for_link(nbytes: int, link_class: str) -> int:
    """ppermute chunk count for one rotation hop of ``nbytes`` payload."""
    if link_class == "dcn":
        return max(1, min(MAX_DCN_CHUNKS, -(-nbytes // DCN_CHUNK_BYTES)))
    return 1


def _resolve_link(link_class: Optional[str], axis_name: str) -> str:
    return (link_class if link_class is not None
            else mesh_lib.axis_link_class(axis_name))


def _leaf_bytes(x) -> int:
    return int(x.size) * x.dtype.itemsize


def _quantizable(leaf) -> bool:
    return jnp.issubdtype(leaf.dtype, jnp.floating)


def _ef_zero(block: Slice):
    """EF residual tree for a block: f32 zeros for float leaves, None-like
    zeros (unused) for non-float leaves so tree structures stay aligned."""
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32) if _quantizable(a)
        else jnp.zeros((), jnp.float32), block)


# public alias: models that thread EF state through their own scan carries
# (``ef_state=``) build the initial residual with this
ef_zero = _ef_zero


@scoped("rotation.hop")
def _shift_block(block: Slice, res: Optional[Slice], shift: int,
                 axis_name: str, comm: Optional[quantize.CommConfig],
                 link_class: str, fused: bool = False):
    """One hop of the block pytree: quantized+EF when ``comm`` is active,
    chunked when the link class asks for it, fused ring DMA for float
    leaves when ``fused`` (plain ICI hops only — the caller resolves the
    precedence). Returns (block', res')."""
    if comm is None or not comm.active:
        def send(x):
            if fused and _quantizable(x):
                return ring_dma.hop(x, shift, axis_name)
            return lax_ops.ring_shift(
                x, shift, axis_name,
                num_chunks=chunks_for_link(_leaf_bytes(x), link_class))
        return jax.tree.map(send, block), res

    def send_ef(leaf, r):
        if not _quantizable(leaf):
            return lax_ops.ring_shift(leaf, shift, axis_name), r
        flat = leaf.reshape(-1).astype(jnp.float32)
        block_sz = quantize._block_for(flat.shape[0], comm)
        payload, scale, n, new_r = quantize.ef_encode_flat(
            flat, r.reshape(-1), comm, block_sz)
        n_ax = lax_ops.num_workers(axis_name)
        perm = [(i, (i + shift) % n_ax) for i in range(n_ax)]
        payload = jax.lax.ppermute(payload, axis_name, perm)
        if scale is not None:
            scale = jax.lax.ppermute(scale, axis_name, perm)
        out = quantize.decode_flat(payload, scale, n, comm).reshape(
            leaf.shape).astype(leaf.dtype)
        return out, new_r.reshape(r.shape)

    # flatten/unflatten instead of a tuple-leafed tree.map: block pytrees may
    # themselves contain tuples (kernel SVM rotates an (x, coef) pair)
    leaves_b, treedef = jax.tree.flatten(block)
    leaves_r = jax.tree.flatten(res)[0]
    sent = [send_ef(lb, lr) for lb, lr in zip(leaves_b, leaves_r)]
    new_block = jax.tree.unflatten(treedef, [s[0] for s in sent])
    new_res = jax.tree.unflatten(treedef, [s[1] for s in sent])
    return new_block, new_res


def rotate_scan(
    body: Callable[[Carry, Slice, jax.Array], Tuple[Carry, Slice]],
    carry: Carry,
    model_block: Slice,
    num_steps: int,
    axis_name: str = WORKERS,
    shift: int = 1,
    comm: Optional[quantize.CommConfig] = None,
    link_class: Optional[str] = None,
    fused_dma: bool = False,
    ef_state: Optional[Slice] = None,
):
    """Unpipelined rotation loop: compute on the block, then shift it.

    ``body(carry, block, step) -> (carry, updated_block)``. After ``num_steps`` =
    num_workers, every worker has seen (and updated) every model block once and each
    block is home again. This is Harp's plain ``rotate()`` loop
    (LocalGlobalSyncCollective.rotate:710 called per iteration).

    ``shift=0`` skips the permute entirely: for a ``body`` that performs the
    hop itself (the dense-MF in-kernel ring epilogue returns the block
    already hopped).

    ``comm``/``link_class``: wire-format options (module docstring). The EF
    residual rides in the scan carry; with ``comm`` active the returned
    block is the lossy-wire trajectory (convergence-equivalent, not
    bit-identical — models pin a parity tolerance vs the f32 run).

    ``fused_dma``/``ef_state``: module docstring. With ``ef_state`` passed
    the return is ``(carry, block, ef_state')``; otherwise the historical
    2-tuple.
    """
    link = _resolve_link(link_class, axis_name)
    quant = comm is not None and comm.active
    fused = fused_dma and not quant and link == "ici"
    res0 = (ef_state if ef_state is not None
            else _ef_zero(model_block) if quant else None)

    def step(state, t):
        c, blk, res = state
        c, blk = body(c, blk, t)
        if shift:
            blk, res = _shift_block(blk, res, shift, axis_name, comm, link,
                                    fused=fused)
        return (c, blk, res), None

    (carry, model_block, res), _ = jax.lax.scan(
        step, (carry, model_block, res0), jnp.arange(num_steps))
    if ef_state is not None:
        return carry, model_block, res
    return carry, model_block


def pipelined_rotation(
    body: Callable[[Carry, Slice, jax.Array], Tuple[Carry, Slice]],
    carry: Carry,
    slice_a: Slice,
    slice_b: Slice,
    num_micro_steps: int,
    axis_name: str = WORKERS,
    shift: int = 1,
    comm: Optional[quantize.CommConfig] = None,
    link_class: Optional[str] = None,
    fused_dma: bool = False,
    ef_state: Optional[Tuple[Slice, Slice]] = None,
):
    """Double-buffered rotation: compute on one slice while the other is in flight.

    The model is split into two slices (Harp: numModelSlices=2). Micro-step t:

      1. ``body`` updates the *resident* slice;
      2. its ``ppermute`` to the next worker is issued;
      3. the slice issued at t-1 becomes resident for t+1.

    For a full epoch (every slice block visits every worker once) use
    ``num_micro_steps = 2 * num_workers``; slices land back on their home workers.

    Returns (carry, slice_a', slice_b') with both slices at their original
    positions when num_micro_steps is a multiple of 2*num_workers.

    ``shift=0``: see :func:`rotate_scan` (slices still swap
    resident/inflight roles; the body moves them between workers).

    ``comm``/``link_class``: wire-format options (module docstring). One EF
    residual per (sender, slice family): sends alternate the two slice
    families, so the residuals ride the same resident/inflight seat swap
    the slices do — slice A's encode error is re-sent with the next
    A-family send, never injected into B's coordinates (and slices of
    different shapes each keep a correctly-shaped residual).

    ``fused_dma``/``ef_state``: module docstring. ``ef_state`` is the
    ``(residual_a, residual_b)`` pair; when passed the return is
    ``(carry, slice_a', slice_b', ef_state')``.
    """
    link = _resolve_link(link_class, axis_name)
    quant = comm is not None and comm.active
    fused = fused_dma and not quant and link == "ici"
    if ef_state is not None:
        res_a0, res_b0 = ef_state
    else:
        res_a0 = _ef_zero(slice_a) if quant else None
        res_b0 = _ef_zero(slice_b) if quant else None

    def step(state, t):
        c, resident, inflight, res_res, res_inf = state
        c, updated = body(c, resident, t)
        outgoing = updated
        if shift:
            outgoing, res_res = _shift_block(updated, res_res, shift,
                                             axis_name, comm, link,
                                             fused=fused)
        # inflight was issued last step; it is resident for the next step. XLA sees
        # `outgoing` unused until step t+1 → overlaps the permute with t+1's compute.
        # The residuals swap seats in lockstep with their slices.
        return (c, inflight, outgoing, res_inf, res_res), None

    state = (carry, slice_a, slice_b, res_a0, res_b0)
    (carry, sa, sb, res_a, res_b), _ = jax.lax.scan(
        step, state, jnp.arange(num_micro_steps))
    if ef_state is not None:
        return carry, sa, sb, (res_a, res_b)
    return carry, sa, sb


class Rotator:
    """Convenience wrapper holding the rotation config (Harp: dymoro/Rotator).

    Harp's Rotator exposed getRotation(k)/rotate(k) imperative calls; here the
    equivalent is declarative — construct with the schedule shape, call
    :meth:`run` with the per-hop body. Kept as a class so algorithm code reads
    like the reference's. ``comm``/``link_class`` thread to the scan
    implementations (module docstring).
    """

    def __init__(self, num_workers: int, num_slices: int = 2,
                 axis_name: str = WORKERS,
                 comm: Optional[quantize.CommConfig] = None,
                 link_class: Optional[str] = None,
                 fused_dma: bool = False,
                 shift: int = 1):
        if num_slices not in (1, 2):
            raise ValueError("num_slices must be 1 (plain) or 2 (double-buffered)")
        self.num_workers = num_workers
        self.num_slices = num_slices
        self.axis_name = axis_name
        self.comm = comm
        self.link_class = link_class
        self.fused_dma = fused_dma
        # shift=0: the scan never permutes — the body performs the hop
        # ITSELF (the dense-MF in-kernel ring epilogue returns the
        # already-hopped block)
        self.shift = shift

    def run(self, body, carry, slices, epochs: int = 1):
        """Run ``epochs`` full rotations. ``slices``: tuple of model slices
        (length == num_slices)."""
        if self.num_slices == 1:
            (slice_a,) = slices
            carry, out = rotate_scan(body, carry, slice_a,
                                     epochs * self.num_workers, self.axis_name,
                                     shift=self.shift, comm=self.comm,
                                     link_class=self.link_class,
                                     fused_dma=self.fused_dma)
            return carry, (out,)
        sa, sb = slices
        carry, sa, sb = pipelined_rotation(
            body, carry, sa, sb, epochs * 2 * self.num_workers, self.axis_name,
            shift=self.shift, comm=self.comm, link_class=self.link_class,
            fused_dma=self.fused_dma)
        return carry, (sa, sb)
