"""Quantized collectives — wire-format compression for the bandwidth-bound hops.

EQuARX (PAPERS.md, arXiv:2506.17615) shows a quantized AllReduce inside XLA
recovers most of a real mesh's collective bandwidth at negligible accuracy
cost. XLA gives us no hook into its reduction stages, so the same two-stage
decomposition is expressed HERE, at the JAX level, out of primitives whose
wire dtype we control:

  * quantized reduce_scatter = ``all_to_all`` of int8/bf16 chunk payloads
    (+ per-block f32 scales for int8) and a LOCAL f32 dequant-sum — the
    accumulation never happens in the narrow dtype (the repo-wide
    lane_pack/JL202 policy: narrow operands, f32 sums);
  * quantized allgather   = ``all_gather`` of the re-quantized reduced
    chunk (+ scales);
  * quantized allreduce   = the two stages composed (the bandwidth-optimal
    decomposition ``table_ops.aggregate`` already documents for f32);
  * quantized rotate      = ``ppermute`` of the encoded block (+ scales).

Semantics are **dequantize-after-transport**: callers pass f32 and receive
f32 — the wire format changes, the math (f32 accumulation, same combiner)
does not. What DOES change is a bounded per-element quantization error; the
**error-feedback** helpers below carry the encode residual so that error is
re-applied to the next send instead of compounding (EF-SGD: the time-average
of the fed-back error vanishes). Residual state lives

  * in the scan carry of ``rotation.rotate_scan``/``pipelined_rotation``
    for rotation paths (one residual per sender — the standard EF-ring
    formulation), and
  * in model fit state for allreduce paths (KMeans/LDA carry it through
    their iteration scan).

int8 uses symmetric scale-per-block quantization (``CommConfig.block``
elements per f32 scale; blocks adapt down for small payloads so a (K,)
vector never pads to a full block). bf16 is a plain downcast — no scales,
half the bytes, ~8-bit mantissa.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import combiner as combiner_lib

QUANT_MODES = (None, "int8", "bf16")

# guards the scale division; an all-zero block quantizes to zeros exactly
_TINY = 1e-30


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Opt-in wire-format config threaded through the collective layer.

    ``quant=None`` (the default everywhere) keeps every path bit-identical
    to the pre-quantization f32 programs — the collective-budget manifest
    pins that. ``block`` is the int8 scale granularity in elements (ignored
    by bf16)."""

    quant: Optional[str] = None      # None | "int8" | "bf16"
    block: int = 256                 # elements per f32 scale (int8 only)

    def __post_init__(self):
        if self.quant not in QUANT_MODES:
            raise ValueError(
                f"quant must be one of {QUANT_MODES}, got {self.quant!r}")
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")

    @property
    def active(self) -> bool:
        return self.quant is not None


# --------------------------------------------------------------------------- #
# Codecs: flat f32 vector <-> (payload, scales)
# --------------------------------------------------------------------------- #

def _block_for(n: int, comm: CommConfig, chunks: int = 1) -> int:
    """Effective scale-block size: adapt down so every chunk holds at least
    one whole block (a (K,) LDA delta must not pad to 256 elements)."""
    per_chunk = max(1, -(-n // chunks))
    return max(1, min(comm.block, per_chunk))


def encode_flat(flat: jax.Array, comm: CommConfig, block: int
                ) -> Tuple[jax.Array, Optional[jax.Array], int]:
    """Encode a flat f32 vector. Returns (payload, scales-or-None, n).

    int8 payload is (nb, block) with scales (nb,); bf16 payload is the
    padded flat vector itself (no scales). Padding is zeros — exact under
    both codecs, trimmed by :func:`decode_flat`."""
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    if comm.quant == "bf16":
        return flat.astype(jnp.bfloat16), None, n
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    q = jnp.clip(jnp.round(blocks / jnp.maximum(scale, _TINY)[:, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale, n


def decode_flat(payload: jax.Array, scale: Optional[jax.Array], n: int,
                comm: CommConfig) -> jax.Array:
    """Inverse of :func:`encode_flat` — back to a flat f32 vector of len n."""
    if comm.quant == "bf16":
        return payload.astype(jnp.float32)[:n]
    flat = (payload.astype(jnp.float32) * scale[:, None]).reshape(-1)
    return flat[:n]


def ef_encode_flat(flat: jax.Array, residual: jax.Array, comm: CommConfig,
                   block: int):
    """Error-feedback encode: compress (x + residual), return the payload
    plus the NEW residual (what the wire failed to carry this round)."""
    y = flat + residual
    payload, scale, n = encode_flat(y, comm, block)
    return payload, scale, n, y - decode_flat(payload, scale, n, comm)


# --------------------------------------------------------------------------- #
# Packed-row codec: f32 factor rows <-> self-describing int8 rows
# --------------------------------------------------------------------------- #
#
# The SERVING-path codec (ISSUE 17). A factor table row quantizes with one
# symmetric per-ROW scale (the row is the dot-product unit, so a per-row
# scale factors out of the score exactly), and the scale travels INSIDE the
# row as its last 4 bytes (the f32 bitcast to int8). The packed row is one
# homogeneous int8 vector, which is what makes it a drop-in KVStore value
# dtype: it rides `DistributedKV.lookup`'s route-back all_to_all, the
# reshard engine's restore/rebalance rounds, and `push_epoch`'s re-scatter
# with zero extra bookkeeping — the scale can never be separated from the
# row it describes. An all-zero row (a KVStore default / a reshard fill)
# decodes to exactly 0.0: the bitcast of four zero bytes is +0.0f.

ROW_SCALE_BYTES = 4          # one f32 scale, bitcast into the row's tail


def encode_rows_np(rows: np.ndarray) -> np.ndarray:
    """Host-side packed-row encode: f32 ``(..., r)`` -> int8 ``(..., r+4)``.

    Symmetric per-row int8 (``scale = max|row| / 127``), scale appended as
    its 4 raw bytes. Numpy's ``.view`` and the device-side
    ``lax.bitcast_convert_type`` both reinterpret native-endian memory, so
    the round trip is exact (pinned by tests/test_serve_quant.py)."""
    rows = np.asarray(rows, np.float32)
    scale = (np.max(np.abs(rows), axis=-1, keepdims=True)
             / 127.0).astype(np.float32)
    q = np.clip(np.rint(rows / np.maximum(scale, _TINY)),
                -127, 127).astype(np.int8)
    return np.concatenate([q, scale.view(np.int8)], axis=-1)


def decode_rows(packed: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Device-side packed-row split: int8 ``(..., r+4)`` ->
    (``(..., r)`` int8 quantized values, ``(...,)`` f32 per-row scales).
    The scale comes back by bitcast — no arithmetic, bit-exact."""
    q = packed[..., :-ROW_SCALE_BYTES]
    scale = jax.lax.bitcast_convert_type(
        packed[..., -ROW_SCALE_BYTES:], jnp.float32)
    return q, scale


def dequantize_rows(packed: jax.Array) -> jax.Array:
    """Device-side packed-row decode back to f32 ``(..., r)``."""
    q, scale = decode_rows(packed)
    return q.astype(jnp.float32) * scale[..., None]


def packed_row_width(r: int) -> int:
    """Trailing width of a packed int8 row for rank-``r`` factors."""
    return int(r) + ROW_SCALE_BYTES


# --------------------------------------------------------------------------- #
# Quantized axis collectives (call inside shard_map over axis_name)
# --------------------------------------------------------------------------- #

def _check_combiner(combiner, op: str) -> None:
    if combiner.op not in (combiner_lib.Op.SUM, combiner_lib.Op.AVG):
        raise ValueError(
            f"quantized {op} supports SUM/AVG combiners only (dequant-sum "
            f"is the transport-side math), got {combiner.op}")


def rotate_q(x: jax.Array, steps: int, axis_name: str,
             comm: CommConfig) -> jax.Array:
    """Quantized ring-shift: encode, ppermute the payload (+scales for
    int8), decode on arrival. One lossy encode per hop; error feedback for
    repeated hops lives in ``rotation.rotate_scan``'s carry."""
    n_ax = jax.lax.axis_size(axis_name)
    perm = [(i, (i + steps) % n_ax) for i in range(n_ax)]
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    block = _block_for(flat.shape[0], comm)
    payload, scale, n = encode_flat(flat, comm, block)
    payload = jax.lax.ppermute(payload, axis_name, perm)
    if scale is not None:
        scale = jax.lax.ppermute(scale, axis_name, perm)
    return decode_flat(payload, scale, n, comm).reshape(shape).astype(x.dtype)


def allgather_q(x: jax.Array, axis_name: str, comm: CommConfig,
                tiled: bool = True) -> jax.Array:
    """Quantized allgather: each worker's block rides the wire encoded and
    is dequantized on arrival — every worker decodes the SAME payload, so
    the gathered result stays replicated-consistent."""
    w = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1).astype(jnp.float32)
    block = _block_for(flat.shape[0], comm)
    payload, scale, n = encode_flat(flat, comm, block)
    all_payload = jax.lax.all_gather(payload, axis_name)       # (W, ...)
    if scale is not None:
        all_scale = jax.lax.all_gather(scale, axis_name)       # (W, nb)
        flat_all = (all_payload.astype(jnp.float32)
                    * all_scale[..., None]).reshape(w, -1)[:, :n]
    else:
        flat_all = all_payload.astype(jnp.float32).reshape(w, -1)[:, :n]
    out = flat_all.reshape((w,) + x.shape).astype(x.dtype)
    if tiled:
        return out.reshape((w * x.shape[0],) + x.shape[1:])
    return out


def reduce_scatter_q(
    x: jax.Array,
    combiner: combiner_lib.Combiner,
    axis_name: str,
    comm: CommConfig,
    residual: Optional[jax.Array] = None,
):
    """Quantized reduce_scatter: worker w receives the f32-accumulated
    combination of every worker's chunk w. Chunks ride the wire encoded
    through ONE all_to_all (+ one for int8 scales); the sum runs in f32
    AFTER dequantization (per-source scales), never in the narrow dtype.

    ``residual`` (shaped like x, f32): error-feedback state — compress
    (x + residual) and return the new residual alongside the result."""
    _check_combiner(combiner, "reduce_scatter")
    w = jax.lax.axis_size(axis_name)
    p = x.shape[0]
    if p % w:
        raise ValueError(f"leading dim {p} must divide over {w} workers")
    shape_out = (p // w,) + x.shape[1:]
    chunks = x.reshape((w, -1)).astype(jnp.float32)           # (W, E)
    e = chunks.shape[1]
    block = _block_for(e, comm)
    if residual is not None:
        res_chunks = residual.reshape((w, -1)).astype(jnp.float32)
        y = chunks + res_chunks
    else:
        y = chunks
    # encode each destination chunk (vmap keeps one (W, nb, block) payload)
    enc = jax.vmap(lambda c: encode_flat(c, comm, block)[:2])
    payload, scale = enc(y)
    n = e
    if residual is not None:
        if scale is not None:
            dec_all = (payload.astype(jnp.float32)
                       * scale[..., None]).reshape(w, -1)[:, :n]
        else:
            dec_all = payload.astype(jnp.float32).reshape(w, -1)[:, :n]
        new_res = (y - dec_all).reshape(residual.shape).astype(residual.dtype)
    payload = jax.lax.all_to_all(payload, axis_name, split_axis=0,
                                 concat_axis=0)               # (W, ...) from
    if scale is not None:
        scale = jax.lax.all_to_all(scale, axis_name, split_axis=0,
                                   concat_axis=0)
        flat_sum = jnp.sum(payload.astype(jnp.float32) * scale[..., None],
                           axis=0).reshape(-1)[:n]
    else:
        flat_sum = jnp.sum(payload.astype(jnp.float32), axis=0)[:n]
    if combiner.op is combiner_lib.Op.AVG:
        flat_sum = flat_sum / w
    out = flat_sum.reshape(shape_out).astype(x.dtype)
    if residual is not None:
        return out, new_res
    return out


def allreduce_q(
    x: jax.Array,
    combiner: combiner_lib.Combiner,
    axis_name: str,
    comm: CommConfig,
    residual: Optional[jax.Array] = None,
):
    """Quantized allreduce: quantized reduce_scatter + quantized allgather
    over the flattened payload — the EQuARX two-stage decomposition at the
    JAX level. Wire bytes ≈ f32 allreduce / 4 (int8 + scale overhead) or
    / 2 (bf16); the result is identical (replicated) on every worker.

    Error feedback covers BOTH stages when ``residual`` (shaped like x,
    f32) is passed: stage-1 encode errors land in the residual for every
    element, and this worker's stage-2 re-encode error is folded into its
    own chunk's slice — the residual lives entirely in x's domain."""
    _check_combiner(combiner, "allreduce")
    w = jax.lax.axis_size(axis_name)
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    cpw = -(-n // w)                         # elements per worker chunk
    pad = w * cpw - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    stacked = flat.reshape(w, cpw)
    block = _block_for(cpw, comm)
    if residual is not None:
        res_flat = residual.reshape(-1).astype(jnp.float32)
        if pad:
            res_flat = jnp.concatenate(
                [res_flat, jnp.zeros((pad,), jnp.float32)])
        y = stacked + res_flat.reshape(w, cpw)
    else:
        y = stacked
    enc = jax.vmap(lambda c: encode_flat(c, comm, block)[:2])
    payload, scale = enc(y)
    if residual is not None:
        if scale is not None:
            dec_all = (payload.astype(jnp.float32)
                       * scale[..., None]).reshape(w, -1)[:, :cpw]
        else:
            dec_all = payload.astype(jnp.float32).reshape(w, -1)[:, :cpw]
        err1 = y - dec_all                                    # (W, cpw)
    payload = jax.lax.all_to_all(payload, axis_name, split_axis=0,
                                 concat_axis=0)
    if scale is not None:
        scale = jax.lax.all_to_all(scale, axis_name, split_axis=0,
                                   concat_axis=0)
        own = jnp.sum(payload.astype(jnp.float32) * scale[..., None],
                      axis=0).reshape(-1)[:cpw]
    else:
        own = jnp.sum(payload.astype(jnp.float32), axis=0).reshape(-1)[:cpw]
    if combiner.op is combiner_lib.Op.AVG:
        own = own / w
    # stage 2: re-encode the reduced chunk, allgather
    payload2, scale2, _ = encode_flat(own, comm, block)
    all_p2 = jax.lax.all_gather(payload2, axis_name)
    if scale2 is not None:
        all_s2 = jax.lax.all_gather(scale2, axis_name)
        full = (all_p2.astype(jnp.float32)
                * all_s2[..., None]).reshape(w, -1)[:, :cpw]
    else:
        full = all_p2.astype(jnp.float32).reshape(w, -1)[:, :cpw]
    out = full.reshape(-1)[:n].reshape(shape).astype(x.dtype)
    if residual is not None:
        err2 = own - decode_flat(payload2, scale2, cpw, comm)  # own chunk
        wid = jax.lax.axis_index(axis_name)
        err = err1.at[wid].add(err2)      # fold stage-2 error into own slice
        new_res = err.reshape(-1)[:n].reshape(residual.shape).astype(
            residual.dtype)
        return out, new_res
    return out


def zeros_residual(x) -> jax.Array:
    """Fresh f32 error-feedback state shaped like ``x`` (models put this in
    their fit carry; rotation puts it in the scan carry)."""
    return jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), x)


# --------------------------------------------------------------------------- #
# Wire accounting (bench + PERF stage math; jaxlint measures traced programs)
# --------------------------------------------------------------------------- #

def wire_bytes_per_element(comm: Optional[CommConfig], n: int = 0) -> float:
    """Bytes each payload element occupies on the wire: 4 (f32), 2 (bf16),
    or 1 + 4/block (int8 + amortized f32 scale, at the effective block for
    an n-element payload)."""
    if comm is None or not comm.active:
        return 4.0
    if comm.quant == "bf16":
        return 2.0
    block = _block_for(n or comm.block, comm)
    return 1.0 + 4.0 / block
