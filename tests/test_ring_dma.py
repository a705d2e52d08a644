"""Fused ring DMA engine tests (ISSUE 9).

The engine's whole contract is "moves bytes, never rounds them": on the
8-worker CPU mesh every fused schedule must be BITWISE the ppermute
schedule (the TPU kernels share the same semantics — ``chip_smoke.py --leg
multichip_ring`` exercises those on four chips). Plus the budget-gate contract:
fused hops trace as the tagged ``fused_dma`` kind, and a fused target
silently reverting to bare ppermute fails JL201/JL203.
"""

import dataclasses
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.collectives import lax_ops, rotation, table_ops
from harp_tpu.models import als, lda, nn
from harp_tpu.ops import ring_dma
from harp_tpu.parallel import ring_attention

W = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- engine primitives ------------------------------------------------------


@pytest.mark.parametrize("shift", [1, 2, -1])
def test_fused_hop_matches_rotate_bitwise(session, rng, shift):
    x = rng.standard_normal((W, 5, 3)).astype(np.float32)
    fused = session.run(lambda a: ring_dma.hop(a, shift), session.scatter(x),
                        in_specs=(session.shard(),),
                        out_specs=session.shard())
    ref = session.run(lambda a: lax_ops.rotate(a, shift), session.scatter(x),
                      in_specs=(session.shard(),), out_specs=session.shard())
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


def test_fused_hop_is_exact_for_int_leaves(session):
    x = np.arange(W * 4, dtype=np.int32).reshape(W, 4)
    fused = session.run(lambda a: ring_dma.hop(a, 1), session.scatter(x),
                        in_specs=(session.shard(),),
                        out_specs=session.shard())
    np.testing.assert_array_equal(np.asarray(fused), np.roll(x, 1, axis=0))


def test_ring_allgather_matches_all_gather_bitwise(session, rng):
    x = rng.standard_normal((W * 2, 3)).astype(np.float32)
    fused = session.run(lambda a: ring_dma.ring_allgather(a)[None],
                        session.scatter(x), in_specs=(session.shard(),),
                        out_specs=session.replicate())
    ref = session.run(
        lambda a: jax.lax.all_gather(a, "workers", tiled=True)[None],
        session.scatter(x), in_specs=(session.shard(),),
        out_specs=session.replicate())
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


def test_lax_ops_allgather_fused_tiled_and_untiled(session, rng):
    x = rng.standard_normal((W * 2, 3)).astype(np.float32)
    for tiled in (True, False):
        fused = session.run(
            lambda a: lax_ops.allgather(a, tiled=tiled, fused=True)[None],
            session.scatter(x), in_specs=(session.shard(),),
            out_specs=session.replicate())
        ref = session.run(
            lambda a: lax_ops.allgather(a, tiled=tiled)[None],
            session.scatter(x), in_specs=(session.shard(),),
            out_specs=session.replicate())
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


def test_table_allgather_fused_with_partitioner(session, rng):
    from harp_tpu.combiner import SUM
    from harp_tpu.partitioner import ModuloPartitioner
    from harp_tpu.table import Dist, Table

    data = rng.standard_normal((W, 4)).astype(np.float32)
    part = ModuloPartitioner(W, W)

    def gather(fused):
        def f(x):
            t = Table(x, SUM, Dist.SHARDED, W, W, "t")
            return table_ops.allgather(t, part, fused=fused).data[None]

        return session.run(f, session.scatter(data),
                           in_specs=(session.shard(),),
                           out_specs=session.replicate())

    np.testing.assert_array_equal(np.asarray(gather(True)),
                                  np.asarray(gather(False)))


# -- rotation schedules -----------------------------------------------------


def test_rotate_scan_fused_bitwise_mixed_tree(session, rng):
    """Float leaves ride the engine, int leaves the lax path — the fused
    trajectory (blocks, carry) must equal the unfused one bitwise."""
    f = rng.standard_normal((W, 4)).astype(np.float32)
    i = np.arange(W, dtype=np.int32).reshape(W, 1)

    def body(c, blk, t):
        bf, bi = blk
        return c + jnp.sum(bf) + jnp.sum(bi), (bf * 1.001 + 0.1, bi + 1)

    def run(fused):
        def fn(bf, bi):
            c, (of, oi) = rotation.rotate_scan(
                body, jnp.zeros(()), (bf, bi), W, fused_dma=fused)
            return c[None], of, oi

        return session.spmd(fn, in_specs=(session.shard(),) * 2,
                            out_specs=(session.shard(),) * 3)(f, i)

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipelined_rotation_fused_bitwise(session, rng):
    a = rng.standard_normal((W, 3)).astype(np.float32)
    b = rng.standard_normal((W, 3)).astype(np.float32)

    def body(c, blk, t):
        return c + jnp.sum(blk), blk + 0.5

    def run(fused):
        def fn(ba, bb):
            c, sa, sb = rotation.pipelined_rotation(
                body, jnp.zeros(()), ba, bb, 2 * W, fused_dma=fused)
            return c[None], sa, sb

        return session.spmd(fn, in_specs=(session.shard(),) * 2,
                            out_specs=(session.shard(),) * 3)(a, b)

    for x, y in zip(run(False), run(True)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_rotate_scan_ef_state_threads_through(session, rng):
    """ef_state in → updated ef_state out, and re-feeding it continues the
    EF chain (the LDA epoch-carry contract)."""
    from harp_tpu.collectives import quantize

    comm = quantize.CommConfig(quant="int8")
    x = rng.standard_normal((W, 256)).astype(np.float32)

    def body(c, blk, t):
        return c, blk

    def fn(bx):
        res = rotation.ef_zero(bx)
        _, out1, res1 = rotation.rotate_scan(body, jnp.zeros(()), bx, W,
                                             comm=comm, ef_state=res)
        _, out2, res2 = rotation.rotate_scan(body, jnp.zeros(()), out1, W,
                                             comm=comm, ef_state=res1)
        return out2, res1, res2

    out2, res1, res2 = session.spmd(
        fn, in_specs=(session.shard(),),
        out_specs=(session.shard(),) * 3)(x)
    # residuals are live (nonzero) and shaped like the block
    assert np.asarray(res1).shape == x.shape
    assert np.abs(np.asarray(res1)).max() > 0
    # after 2 full EF rings the block tracks the exact one within codec tol
    np.testing.assert_allclose(np.asarray(out2), x, atol=0.2)


# -- ring attention ---------------------------------------------------------


@pytest.mark.parametrize("l_local,causal,flash", [
    (8, True, False),          # aligned, XLA hop
    (8, False, False),
    (7, True, True),           # PRIME local length through the flash kernel
    (16, False, True),         # aligned through the flash kernel
])
def test_ring_attention_fused_parity(session, rng, l_local, causal, flash):
    from harp_tpu.parallel import ring_attention as ra

    h, dh = 4, 8
    l_full = W * l_local
    q = rng.standard_normal((l_full, h, dh)).astype(np.float32)
    k = rng.standard_normal((l_full, h, dh)).astype(np.float32)
    v = rng.standard_normal((l_full, h, dh)).astype(np.float32)
    ref = np.stack([np.asarray(ra.reference_attention(
        q[:, i], k[:, i], v[:, i], causal)) for i in range(h)], axis=1)
    outs = {}
    for fused in (False, True):
        out = session.run(
            lambda a, b, c: ra.ring_attention_mha(
                a, b, c, causal, use_flash=flash, interpret=flash,
                fused_dma=fused),
            session.scatter(jnp.asarray(q)), session.scatter(jnp.asarray(k)),
            session.scatter(jnp.asarray(v)),
            in_specs=(session.shard(),) * 3, out_specs=session.shard())
        outs[fused] = np.asarray(out)
        np.testing.assert_allclose(outs[fused], ref, rtol=2e-4, atol=2e-5)
    # and the two transports agree bitwise with each other
    np.testing.assert_array_equal(outs[False], outs[True])


def test_stream_hop_sends_whole_tiles_only():
    """A send carries a whole number of the kernel's column tiles, and the
    sends cover the block: 4 tiles a send cannot cover 27."""
    with pytest.raises(ValueError, match="do not divide"):
        ring_dma.stream_hop(None, None, None, None, True, 0, 256, 27,
                            "workers", 4, tiles_per_send=4)


def test_flash_ring_hop_rejects_bad_modes():
    from harp_tpu.ops import pallas_kernels as pk

    x = jnp.zeros((16, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="return_stats"):
        pk.flash_attention_pallas(x, x, x, ring_hop=True)
    with pytest.raises(ValueError, match="interpret"):
        pk.flash_attention_pallas(x, x, x, ring_hop=True,
                                  return_stats=True, interpret=True)


# -- model-level fused parity ----------------------------------------------


def test_sgd_mf_fused_bitwise(session, rng):
    from harp_tpu.models import sgd_mf

    n = 400
    rows = rng.integers(0, 64, size=n)
    cols = rng.integers(0, 48, size=n)
    vals = rng.normal(size=n).astype(np.float32)
    for ns in (1, 2):
        outs = []
        for fused in (False, True):
            m = sgd_mf.SGDMF(session, sgd_mf.SGDMFConfig(
                rank=8, epochs=3, minibatches_per_hop=2, num_slices=ns,
                fused_dma=fused))
            outs.append(m.fit(rows, cols, vals, 64, 48))
        for a, b in zip(outs[0], outs[1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lda_fused_bitwise(session, rng):
    from harp_tpu.models import lda

    docs = rng.integers(0, 96, size=(16, 12))
    for ns in (1, 2):
        outs = []
        for fused in (False, True):
            m = lda.LDA(session, lda.LDAConfig(
                num_topics=4, vocab=96, epochs=3, num_model_slices=ns,
                fused_dma=fused))
            outs.append(m.fit(docs, seed=0))
        for a, b in zip(outs[0], outs[1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lda_quant_wt_convergence_parity_cvb0(session, rng):
    """The satellite quantized wt-block rotation: CVB0 is deterministic, so
    the f32-vs-quantized ll delta is PURE wire quantization error. The
    whole (vpb, K) count block rides int8 with EF in the epoch carry —
    tolerance is accordingly looser than the topic-total-only quant test
    (tiny tier-1 blocks quantize coarsely), and the chain must still
    IMPROVE like the f32 one."""
    from harp_tpu.models import lda

    docs = rng.integers(0, 96, size=(16, 12))
    base = lda.LDA(session, lda.LDAConfig(num_topics=4, vocab=96, epochs=4,
                                          method="cvb0"))
    _, _, ll0 = base.fit(docs, seed=0)
    ll0 = np.asarray(ll0)
    for codec in ("int8", "bf16"):
        for ns in (1, 2):
            m = lda.LDA(session, lda.LDAConfig(
                num_topics=4, vocab=96, epochs=4, method="cvb0",
                quant=codec, quant_wt=True, num_model_slices=ns))
            _, _, ll = m.fit(docs, seed=0)
            ll = np.asarray(ll)
            # trajectory parity: pinned at 20% relative (measured r10:
            # 1-13% across codecs/slice counts at this tier-1 shape — the
            # (12, 4) tier-1 wt blocks quantize coarsely; bigger blocks
            # only shrink the relative error)
            np.testing.assert_allclose(ll, ll0, rtol=0.2)


def test_lda_quant_wt_requires_quant(session):
    from harp_tpu.models import lda

    with pytest.raises(ValueError, match="quant_wt"):
        lda.LDA(session, lda.LDAConfig(num_topics=4, vocab=96,
                                       quant_wt=True))


# -- budget gate: fused targets pin their bytes -----------------------------


def test_fused_hop_name_contract():
    from tools.jaxlint import checkers_jaxpr

    assert checkers_jaxpr.FUSED_HOP_PREFIX == ring_dma.FUSED_HOP_NAME


def test_fused_trace_targets_pin_fused_dma_bytes(session):
    from tools.jaxlint import checkers_jaxpr

    counts, dtype_bad, nbytes = checkers_jaxpr.trace_target("lda_cgs_fused")
    assert dtype_bad == []
    # the wt hop is booked as fused_dma, NOT ppermute...
    assert counts.get("fused_dma", 0) >= 1
    assert counts.get("ppermute", 0) == 0
    # ...and moves exactly the bytes the unfused twin's ppermute moved
    counts0, _, nbytes0 = checkers_jaxpr.trace_target("lda_cgs")
    assert nbytes["fused_dma"] == nbytes0["ppermute"]
    assert sum(nbytes.values()) == sum(nbytes0.values())
    # the committed manifest carries the explicit fused row
    with open(os.path.join(REPO, checkers_jaxpr.BUDGET_FILE)) as f:
        manifest = json.load(f)
    row = manifest["targets"]["lda_cgs_fused"]
    assert row["fused_dma_bytes_per_step"] == nbytes["fused_dma"] > 0
    # quantized-wt satellite: its rotation wire sits well below the f32 one
    quant_row = manifest["targets"]["lda_cgs_quantwt_int8"]
    assert quant_row["bytes_per_step"] < row["bytes_per_step"]


def test_fused_revert_to_ppermute_fails_budget_gate():
    """ISSUE 9 acceptance: a fused target silently reverting to ppermute
    (the transport swap with identical totals) must fail the gate."""
    from tools.jaxlint import checkers_jaxpr

    with open(os.path.join(REPO, checkers_jaxpr.BUDGET_FILE)) as f:
        manifest = json.load(f)
    row = manifest["targets"]["lda_cgs_fused"]
    counts = dict(row["collectives"])
    nbytes = dict(row["bytes_by_kind"])
    # simulate the revert: the fused hop becomes a bare ppermute — same
    # bytes, same total, different kind
    counts["ppermute"] = counts.pop("fused_dma")
    nbytes["ppermute"] = nbytes.pop("fused_dma")
    traced = {"lda_cgs_fused": (counts, [], nbytes)}
    findings = checkers_jaxpr.check_budget(REPO, traced)
    mine = [f for f in findings if f.func == "lda_cgs_fused"]
    assert any(f.code == "JL201" for f in mine), mine   # kind drift
    assert any(f.code == "JL203" for f in mine), mine   # byte drift
    # and a manifest row LACKING the fused field while the trace moves
    # fused bytes is itself a finding
    legacy = {k: v for k, v in row.items()
              if k != "fused_dma_bytes_per_step"}
    import copy
    doctored = copy.deepcopy(manifest)
    doctored["targets"]["lda_cgs_fused"] = legacy
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        os.makedirs(os.path.join(td, "tools"))
        with open(os.path.join(td, checkers_jaxpr.BUDGET_FILE), "w") as f:
            json.dump(doctored, f)
        traced_ok = {"lda_cgs_fused": (dict(row["collectives"]), [],
                                       dict(row["bytes_by_kind"]))}
        findings = checkers_jaxpr.check_budget(td, traced_ok)
        assert any(f.code == "JL203"
                   and "fused_dma_bytes_per_step" in f.message
                   for f in findings if f.func == "lda_cgs_fused")


# -- no timing-only switch in a production config ---------------------------


@pytest.mark.parametrize("names", [
    [f.name for f in dataclasses.fields(als.ALSConfig)],
    [f.name for f in dataclasses.fields(lda.LDAConfig)],
    [f.name for f in dataclasses.fields(nn.NNConfig)],
    list(inspect.signature(ring_attention.ring_attention_mha).parameters),
], ids=["ALSConfig", "LDAConfig", "NNConfig", "ring_attention_mha"])
def test_no_ablation_switch_in_production_code(names):
    """A switch that gives wrong results so that a stage can be priced by
    difference has no place in a config users set: device time by scope
    (``harp_tpu/telemetry/scopes.py``) prices a stage of the real program."""
    assert not [n for n in names if n.startswith("ablate")]
