"""Fused ring DMA — one async-remote-copy engine behind every ring schedule.

Harp's premise is that the Rotator schedule overlaps communication with
compute ("compute on the slice that arrived while the next one is in
flight"). Through r9 every rotation hop in this reproduction still crossed
the kernel boundary as an XLA-level ``ppermute``: the payload takes an HBM
round trip into the collective's staging buffer on the sender AND out of it
on the receiver, and nothing overlaps unless XLA's async collective
scheduler finds the slack. The fix — SNIPPETS.md [1], the JAX
distributed-pallas recipe, and the Ring Attention line of work
(arXiv:2310.01889: the KV hop hides entirely behind block compute) — is to
issue the neighbor copy FROM INSIDE a kernel with
``pltpu.make_async_remote_copy``: the DMA engines stream the next shard
into the neighbor's buffer while the MXU chews the current one, and the
payload moves producer-buffer → remote-buffer with no staging copies.

This module is the ONE implementation of that motion (the ``lane_pack``
pattern: one engine, many call sites). Three layers:

* **Kernel-side helpers** — :func:`ring_ready` (credit-exact
  receiver-ready handshake: nobody's DMA may land before its receiver has
  entered the kernel), :func:`start_hop`/:func:`hop_op` (device-id ring
  math + ``make_async_remote_copy`` with ``DeviceIdType.MESH``, returned
  STARTED so the caller computes before ``.wait()`` — the per-hop
  start/wait split), :func:`stream_hop` (a block sent column tile by
  column tile as the kernel's grid finishes them). These are what the
  fused kernels consume: the flash-attention ring epilogue
  (``pallas_kernels._flash_kernel``), the dense-MF hop, which streams the
  updated H block out behind its last stripe
  (``pallas_kernels.dense_mf_hop_pallas``), and the in-kernel ring
  allgather below.
* **Host-level fused ops** — :func:`hop` (one whole-payload ring hop as a
  pallas kernel: barrier, start, wait; HBM→remote-HBM, zero staging) and
  :func:`ring_allgather` (the W−1-hop in-kernel relay, double-buffered
  send/recv semaphores, per-hop recv semaphore array).
* **The fallback contract** — off TPU (the 8-worker virtual CPU mesh every
  tier-1 test and jaxpr budget trace runs on) both ops lower to the
  existing ``lax_ops.rotate`` ring, wrapped in a jit named
  :data:`FUSED_HOP_NAME`. That name is load-bearing: the jaxlint jaxpr
  engine recognizes the tagged call and books its operand bytes as the
  ``fused_dma`` kind (manifest ``fused_dma_bytes_per_step``), so a fused
  schedule that silently reverts to a bare ``ppermute`` shows up as byte
  drift and fails JL201/JL203 — the bytes must not simply vanish from the
  budget when the permute vanishes from the jaxpr.

Semantics are identical on every path: ``hop(x, s)`` delivers the block
previously held by worker ``(id - s) mod W`` (exactly ``lax_ops.rotate``),
bitwise for every dtype — the engine moves bytes, it never rounds them.
Quantized (``CommConfig``) and DCN-chunked hops keep the lax path: a
quantized wire needs the encode/decode programs around the transport
anyway, and DCN hops want ppermute chunk pipelining, not one monolithic
DMA (collectives/rotation.py routes those explicitly).

Collective IDs: every distinct fused collective in a program needs its own
barrier-semaphore identity; the small static registry below keeps them
disjoint (same ID on every worker for the same logical collective).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harp_tpu.collectives import lax_ops
from harp_tpu.parallel.mesh import WORKERS

# The jit name the CPU/interpret fallback wraps the lax rotate in. jaxlint's
# jaxpr walker keys on this exact prefix to book the hop's operand bytes as
# the `fused_dma` kind instead of `ppermute` — renaming it is a budget-
# manifest change (tools/jaxlint/checkers_jaxpr.py).
FUSED_HOP_NAME = "ring_dma_fused_hop"

# Static collective-ID registry: each logical fused collective gets a stable
# ID, identical across workers, distinct across collectives in one program
# (shared barrier semaphores must not alias between, say, a rotation hop and
# the flash epilogue running in the same step).
COLLECTIVE_IDS = {
    "allgather": 2,
    "flash_ring": 3,
    "dense_mf_ring": 4,
}

# Dynamically-allocated IDs for host-level hop() kernels: a program may run
# SEVERAL hop kernels per step (every float leaf of a rotated pytree), and
# two kernels sharing a collective_id share a barrier semaphore — a fast
# neighbor's signal from kernel B could then satisfy a straggler's wait in
# kernel A. Each hop() CALL SITE therefore draws a fresh ID at trace time;
# tracing is deterministic SPMD program construction, so every worker (and
# every process of a multi-host gang building the same program) assigns the
# same IDs in the same order. The range below keeps dynamic IDs clear of
# the static registry; >240 distinct hop call sites in ONE program would
# wrap and alias — far beyond any real schedule.
_HOP_ID_BASE = 16
_HOP_ID_SPAN = 240
_hop_id_counter = [0]


def _next_hop_id() -> int:
    hid = _HOP_ID_BASE + (_hop_id_counter[0] % _HOP_ID_SPAN)
    _hop_id_counter[0] += 1
    return hid


def use_ring_dma() -> bool:
    """Dispatch gate for the fused kernels: the TPU backend. Off TPU the
    engine ALWAYS takes the tagged lax twin (the kernels have no remote-DMA
    lowering there), so tier-1 and the budget traces run the identical
    schedule off-chip."""
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------- #
# Kernel-side engine (use INSIDE a pallas kernel)
# --------------------------------------------------------------------------- #


def _on_axis(axis_name: str, index):
    """MESH device id of the worker at ``index`` along ``axis_name``. The
    dict form leaves every OTHER mesh axis at this device's own coordinate
    — every session mesh has two axes (``workers``, ``model``), and a bare
    ``(index,)`` tuple is refused there ("Number of device ids must match
    the number of mesh axes")."""
    return {axis_name: index}


def ring_neighbor(axis_name: str, num_workers: int, shift: int = 1):
    """(my_id, destination id) for a ring hop of ``shift`` — kernel-side.

    ``num_workers`` is static (pallas kernels cannot psum an axis size);
    ``shift`` is normalized so negative shifts work.
    """
    my = lax.axis_index(axis_name)
    dst = lax.rem(my + (shift % num_workers), num_workers)
    return my, dst


def ring_ready(axis_name: str, num_workers: int, shift: int = 1) -> None:
    """Receiver-ready handshake before a ring-hop DMA — credit-exact.

    A remote copy lands in the receiver's buffer; the send must not start
    until the receiver has ENTERED this kernel (its buffers live, its prior
    reads of any reused allocation done). Each worker signals the worker
    that will SEND to it (``(id − shift) mod W``): "my buffer is ready",
    then waits for the matching signal from its own receiver. The
    accounting is credit-based flow control: one signal produced and one
    consumed per kernel instance per worker, so across a ``lax.scan`` of
    hop kernels a fast worker BLOCKS at iteration t+1 until its receiver
    has entered iteration t+1 — a symmetric both-neighbor barrier with a
    plain wait(2) does NOT have this property (two signals from the fast
    side could satisfy the wait while the slow side never arrived, r10
    review finding). Requires the kernel to carry a ``collective_id``
    (``pltpu.CompilerParams``); concurrent kernels must use DISTINCT ids
    (:func:`_next_hop_id`) so their barrier semaphores never alias."""
    bsem = pltpu.get_barrier_semaphore()
    my = lax.axis_index(axis_name)
    src = lax.rem(my - (shift % num_workers) + num_workers, num_workers)
    pltpu.semaphore_signal(bsem, inc=1, device_id=_on_axis(axis_name, src),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(bsem, 1)


def hop_op(src_ref, dst_ref, send_sem, recv_sem, axis_name: str,
           num_workers: int, shift: int = 1):
    """The (un-started) ring-hop remote-copy descriptor
    ``src_ref → dst_ref@neighbor``. A descriptor is just refs + semaphores,
    so the WAIT side of a start/wait split rebuilds the identical
    descriptor in its own scope (e.g. a later ``pl.when`` branch) and calls
    ``.wait()`` — the pallas double-buffering idiom."""
    _, dst = ring_neighbor(axis_name, num_workers, shift)
    return pltpu.make_async_remote_copy(
        src_ref=src_ref, dst_ref=dst_ref, send_sem=send_sem,
        recv_sem=recv_sem, device_id=_on_axis(axis_name, dst),
        device_id_type=pltpu.DeviceIdType.MESH)


def start_hop(src_ref, dst_ref, send_sem, recv_sem, axis_name: str,
              num_workers: int, shift: int = 1):
    """Start one ring-hop remote copy ``src_ref → dst_ref@neighbor``.

    Returns the STARTED async op — the caller computes on resident data and
    calls ``.wait()`` when it needs the incoming block (the per-hop
    start/wait split that hides hop t+1's DMA behind hop t's compute).
    ``send_sem``/``recv_sem`` are DMA semaphores (double-buffered callers
    pass per-slot entries of a ``SemaphoreType.DMA((2,))`` array)."""
    op = hop_op(src_ref, dst_ref, send_sem, recv_sem, axis_name,
                num_workers, shift)
    op.start()
    return op


def stream_hop(src_ref, dst_ref, send_sem, recv_sem, live, j, tile: int,
               n_tiles: int, axis_name: str, num_workers: int,
               tiles_per_send: int = 1) -> None:
    """One ring hop of a ``(rows, n_tiles * tile)`` block, streamed out of a
    kernel's grid column tile by column tile as the kernel finishes them.

    Call it at every grid step, after the step has stored column tile ``j``
    of ``src_ref``; ``live`` is true on the steps whose tiles are final (the
    dense-MF hop: its last stripe, whose step j is tile j's last update).
    The first live step does the :func:`ring_ready` handshake; every
    ``tiles_per_send``-th live step starts the copy of the tiles stored since
    the last send into the right neighbour's ``dst_ref`` at the same
    columns; the last live step (``j == n_tiles - 1``) waits for every send
    and every receive, one wait a send (a DMA semaphore counts what each
    copy moved). So the block's transfer overlaps the live steps' compute,
    and the last send at least stays exposed. ``tiles_per_send == n_tiles``
    sends the block whole at the last step: nothing overlaps.

    One send and one recv DMA semaphore serve all the copies: a wait takes
    one copy's worth off its semaphore, in whatever order the copies end.
    The copy of step j reads the tiles after the step's stores, in program
    order."""
    if n_tiles % tiles_per_send:
        raise ValueError(f"{tiles_per_send} tiles a send do not divide "
                         f"{n_tiles}")
    cols = tiles_per_send * tile

    def op(c):
        window = pl.ds(c * cols, cols)
        return hop_op(src_ref.at[:, window], dst_ref.at[:, window], send_sem,
                      recv_sem, axis_name, num_workers)

    @pl.when(live & (j == 0))
    def _ready():
        ring_ready(axis_name, num_workers)

    @pl.when(live & (lax.rem(j + 1, tiles_per_send) == 0))
    def _send():
        op(lax.div(j + 1, tiles_per_send) - 1).start()

    @pl.when(live & (j == n_tiles - 1))
    def _drain():
        for c in range(n_tiles // tiles_per_send):
            op(c).wait()


# --------------------------------------------------------------------------- #
# Host-level fused ops + the tagged fallback
# --------------------------------------------------------------------------- #

_FALLBACK_CACHE: dict = {}


def _fallback_hop(axis_name: str, shift: int):
    """The off-TPU lowering: ``lax_ops.rotate`` wrapped in a jit named
    :data:`FUSED_HOP_NAME` so the budget manifest books its bytes as
    ``fused_dma``. Cached per (axis, shift) — one trace per schedule, the
    JL103 jit-in-loop contract."""
    key = (axis_name, shift)
    if key not in _FALLBACK_CACHE:
        def ring_dma_fused_hop(x):
            return lax_ops.rotate(x, shift, axis_name)

        _FALLBACK_CACHE[key] = jax.jit(ring_dma_fused_hop)
    return _FALLBACK_CACHE[key]


def _hop_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis_name: str,
                num_workers: int, shift: int):
    ring_ready(axis_name, num_workers, shift)
    start_hop(x_ref, o_ref, send_sem, recv_sem, axis_name, num_workers,
              shift).wait()


def hop(x: jax.Array, shift: int = 1, axis_name: str = WORKERS
        ) -> jax.Array:
    """One fused ring hop: this worker's block moves to ``(id + shift)``;
    the return value is the block from ``(id - shift)`` — exactly
    ``lax_ops.rotate(x, shift)``, bitwise, on every backend.

    On TPU the payload rides a single in-kernel ``make_async_remote_copy``
    (HBM → remote HBM: the DMA reads the producer's buffer directly, where
    ``ppermute`` costs a staging copy on both ends), after the
    :func:`ring_ready` handshake (jax refuses a ``collective_id`` on a
    kernel that takes no barrier semaphore, so there is no handshake-free
    variant).

    Off TPU: the tagged lax fallback (module docstring)."""
    if not use_ring_dma():
        return _fallback_hop(axis_name, shift)(x)
    nw = lax_ops.num_workers(axis_name)
    if nw == 1:
        return x        # a ring of one: no neighbour, no DMA, no handshake
    kernel = functools.partial(_hop_kernel, axis_name=axis_name,
                               num_workers=nw, shift=shift)
    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
        compiler_params=pltpu.CompilerParams(
            collective_id=_next_hop_id()),
    )(x)


def hop_tree(tree, shift: int = 1, axis_name: str = WORKERS):
    """Ring-hop every leaf of a pytree through the engine (float AND int
    leaves — the engine is exact, so nothing needs the lax path). Each
    leaf's kernel keeps its own :func:`ring_ready` handshake AND its own
    collective ID: inside a scan the same buffers recur every iteration,
    and the per-kernel credit handshake is what guarantees no DMA lands in
    a buffer a slower neighbor is still consuming."""
    return jax.tree.map(lambda leaf: hop(leaf, shift, axis_name), tree)


def _allgather_kernel(x_ref, o_ref, copy_sem, send_sem, recv_sems, *,
                      axis_name: str, num_workers: int):
    """One grid step of the in-kernel ring allgather (grid = W−1 hops).

    Step t forwards the block received at t−1 (slot ``my − t``) to the right
    neighbor's same slot — the classic relay: after W−1 steps every worker
    holds every block. Double-buffered in the OUTPUT buffer itself (each
    slot is written exactly once per worker, then only read), with one send
    semaphore reused per step and a DISTINCT recv semaphore per step so a
    fast sender's step-t+1 copy can never be confused with step t's."""
    t = pl.program_id(0)
    my, right = ring_neighbor(axis_name, num_workers, 1)

    @pl.when(t == 0)
    def _first():
        # own block into its slot, then the receiver-ready handshake:
        # nobody sends until its receiver's output buffer is live (later
        # steps are sequenced by the per-step recv semaphores)
        local = pltpu.make_async_copy(x_ref, o_ref.at[my], copy_sem)
        local.start()
        local.wait()
        ring_ready(axis_name, num_workers, 1)

    slot = lax.rem(my - t + num_workers, num_workers)
    op = pltpu.make_async_remote_copy(
        src_ref=o_ref.at[slot], dst_ref=o_ref.at[slot],
        send_sem=send_sem, recv_sem=recv_sems.at[t],
        device_id=_on_axis(axis_name, right),
        device_id_type=pltpu.DeviceIdType.MESH)
    op.start()
    op.wait()


def ring_allgather(x: jax.Array, axis_name: str = WORKERS) -> jax.Array:
    """Fused ring allgather: every worker ends with all blocks, tiled along
    axis 0 in worker order — bitwise ``jax.lax.all_gather(tiled=True)``.

    On TPU: W−1 in-kernel hops relaying through the output buffer (module
    docstring). Off TPU: the same relay as W−1 tagged fallback hops
    assembled with dynamic slot writes, so the budget manifest prices the
    fused allgather at its true (W−1)·block wire volume."""
    if x.ndim == 0:
        raise ValueError("ring_allgather needs at least one axis to tile")
    nw = lax_ops.num_workers(axis_name)
    if nw == 1:
        return x
    if not use_ring_dma():
        wid = lax_ops.worker_id(axis_name)
        out = jnp.zeros((nw,) + x.shape, x.dtype)
        out = lax.dynamic_update_slice_in_dim(out, x[None], wid, 0)
        cur = x
        for t in range(1, nw):
            cur = _fallback_hop(axis_name, 1)(cur)
            src = lax.rem(wid - t + nw, nw)
            out = lax.dynamic_update_slice_in_dim(out, cur[None], src, 0)
        return out.reshape((nw * x.shape[0],) + x.shape[1:])
    kernel = functools.partial(_allgather_kernel, axis_name=axis_name,
                               num_workers=nw)
    out = pl.pallas_call(
        kernel,
        grid=(nw - 1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((nw,) + x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA((nw - 1,))],
        compiler_params=pltpu.CompilerParams(
            collective_id=COLLECTIVE_IDS["allgather"]),
    )(x)
    return out.reshape((nw * x.shape[0],) + x.shape[1:])
