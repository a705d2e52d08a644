"""The dense rating planes ``models/als.py`` and ``models/ccd.py`` train on.

A rating matrix is kept twice on the device as NaN-encoded bfloat16 planes
(NaN = unobserved; bfloat16 keeps 8 bits of a value, half stars are exact):
the row side's ``(u_pad, i_pad)``, sharded by its rows, and the other side's,
its transpose, sharded by ITS rows, so that each half-step of either model
owns whole rows of the side it updates. Entities stay in natural order;
rows past the matrix's own are all NaN.

The row side's plane is built straight in bfloat16 on the host; the other
side's is made ON THE DEVICE (entries are already deduped): a strided host
transpose of 1.5 GB and a second transfer were half of ``prepare`` at the
MovieLens-10M shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.session import HarpSession


def dense_plane(rows, cols, vals, u_pad: int, i_pad: int) -> np.ndarray:
    """The row side's plane ``(u_pad, i_pad)`` on the host, from deduped
    entries."""
    import ml_dtypes

    u_plane = np.full((u_pad, i_pad), np.nan, ml_dtypes.bfloat16)
    u_plane[rows, cols] = vals.astype(ml_dtypes.bfloat16)
    return u_plane


def place_dense_planes(sess: HarpSession, fns: dict, u_plane: np.ndarray):
    """Both planes on the device, each sharded by its own rows: the row
    side's placed, the other side's transposed there (``fns`` keeps the
    jitted transpose under ``"transpose"``)."""
    u_dev = sess.scatter(jnp.asarray(u_plane, jnp.bfloat16))
    if "transpose" not in fns:
        fns["transpose"] = jax.jit(
            jnp.transpose, out_shardings=sess.sharding(sess.shard()))
    with telemetry.phase("session.run"):
        i_dev = fns["transpose"](u_dev)
    return u_dev, i_dev
