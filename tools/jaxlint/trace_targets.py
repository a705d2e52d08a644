"""Model step-function trace registry for the jaxpr engine.

Each target builds a model at TIER-1 shapes (the same tiny configs the test
suite runs on the 8-worker virtual CPU mesh) and returns the compiled step
callable plus already-placed inputs, so ``jax.make_jaxpr`` can trace the
whole training program WITHOUT executing it. The traced collective counts
are what ``tools/collective_budget.json`` pins — an extra psum per step (or
a variant silently changing its collective kind) is a performance-contract
drift exactly like a bench-number regression (arXiv:2112.01075 treats
per-step collective counts as a first-class redistribution contract).

Prepare-side work DOES run on host+device (tiny device_puts); the step
program itself is only traced. Keep shapes small — every target is traced
in tier-1.
"""

from __future__ import annotations

import os
import sys
from typing import Callable, Dict, Tuple

NUM_WORKERS = 8


def ensure_cpu_mesh() -> None:
    """Force the tier-1 tracing platform: 8 virtual CPU devices.

    Mirrors tests/conftest.py. Must run before jax initializes a backend;
    inside pytest the conftest has already done the identical setup.
    """
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{NUM_WORKERS}").strip()
    import jax

    # before any backend initializes (conftest does the same): tracing
    # must not hold a real accelerator
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", False)
    if len(jax.devices()) < NUM_WORKERS:
        raise RuntimeError(
            f"jaxlint tracing needs {NUM_WORKERS} virtual CPU devices but "
            f"found {len(jax.devices())} — jax initialized before "
            f"ensure_cpu_mesh() could set XLA_FLAGS")


def _session():
    from harp_tpu.session import HarpSession

    return HarpSession(num_workers=NUM_WORKERS)


def _rng():
    import numpy as np

    return np.random.default_rng(0)


# -- builders: () -> (callable, args) --------------------------------------


def _kmeans(comm: str, quant=None):
    def build():
        from harp_tpu.models import kmeans as km

        sess = _session()
        model = km.KMeans(sess, km.KMeansConfig(8, 16, iterations=2,
                                                comm=comm, quant=quant))
        rng = _rng()
        pts = rng.normal(size=(64, 16)).astype("float32")
        p, c = model.prepare(pts, pts[:8].copy())
        return model._fit, (p, c)

    return build


def _lda(**cfg_kw):
    def build():
        from harp_tpu.models import lda

        sess = _session()
        model = lda.LDA(sess, lda.LDAConfig(num_topics=4, vocab=96,
                                            epochs=2, **cfg_kw))
        docs = _rng().integers(0, 96, size=(16, 12))
        key, data, seed, _meta = model.prepare(docs, seed=0)
        return model._fns[key], (*data, seed)

    return build


def _lda_subblock():
    from harp_tpu.models import lda

    sess = _session()
    model = lda.LDA(sess, lda.LDAConfig(num_topics=4, vocab=2048, epochs=2,
                                        vocab_sub_block=128))
    docs = _rng().integers(0, 2048, size=(16, 12))
    key, data, seed, _meta = model.prepare(docs, seed=0)
    return model._fns[key], (*data, seed)


def _sgd_mf(quant=None, fused_dma=False):
    def build():
        from harp_tpu.models import sgd_mf

        sess = _session()
        cfg = sgd_mf.SGDMFConfig(rank=8, lam=0.01, lr=0.1, epochs=2,
                                 minibatches_per_hop=2, quant=quant,
                                 fused_dma=fused_dma)
        model = sgd_mf.SGDMF(sess, cfg)
        rng = _rng()
        n = 400
        rows = rng.integers(0, 64, size=n)
        cols = rng.integers(0, 48, size=n)
        vals = rng.normal(size=n).astype("float32")
        layout, data, w0, h0, meta = model.prepare(rows, cols, vals, 64, 48)
        key = model._program(layout, cfg.minibatches_per_hop, cfg.epochs,
                             meta[6])
        return model._compiled[key], (*data, w0, h0)

    return build


def _als():
    from harp_tpu.models import als

    sess = _session()
    cfg = als.ALSConfig(rank=8, lam=0.05, iterations=2, implicit=False)
    model = als.ALS(sess, cfg)
    rng = _rng()
    n = 400
    rows = rng.integers(0, 80, size=n)
    cols = rng.integers(0, 64, size=n)
    vals = rng.normal(size=n).astype("float32")
    key, placed, _, _ = model.prepare(rows, cols, vals, 80, 64)
    return model._fns[key], placed


def _wdamds():
    from harp_tpu.models import mds

    sess = _session()
    # two iterations of a 3-step CG: the counts per CG step and per
    # iteration stand apart in the row
    model = mds.WDAMDS(sess, mds.MDSConfig(dim=3, iterations=2, cg_iters=3))
    rng = _rng()
    pts = rng.normal(size=(64, 5)).astype("float32")
    dist = mds.distance_matrix(pts)
    key, placed = model.prepare(dist, (dist < 3.0).astype("float32"))
    return model._fns[key], placed


def _pagerank():
    from harp_tpu.models import pagerank as pr

    sess = _session()
    cfg = pr.PageRankConfig(iterations=2)
    rng = _rng()
    n_edges, n_vertices = 200, 64
    src = rng.integers(0, n_vertices, size=n_edges).astype("int32")
    dst = rng.integers(0, n_vertices, size=n_edges).astype("int32")
    nbr, mask, deg = pr.pad_out_edges(src, dst, n_vertices, sess.num_workers)
    v_pad = nbr.shape[0]
    fn = sess.spmd(
        lambda a, b, c: pr._pagerank(a, b, c, n_vertices, v_pad, cfg),
        in_specs=(sess.shard(),) * 3,
        out_specs=(sess.replicate(), sess.replicate()))
    return fn, (sess.scatter(nbr), sess.scatter(mask), sess.scatter(deg))


def _nn():
    import jax.numpy as jnp

    from harp_tpu.models import nn

    sess = _session()
    cfg = nn.NNConfig(layers=(8,), num_classes=3, lr=0.1, batch_size=8,
                      epochs=2)
    rng = _rng()
    x = rng.normal(size=(64, 10)).astype("float32")
    y = rng.integers(0, 3, size=64).astype("int32")
    params0 = nn.init_params((10, 8, 3), seed=0)
    fn = sess.spmd(
        lambda a, t, p: nn._train(a, t, p, cfg),
        in_specs=(sess.shard(), sess.shard(), sess.replicate()),
        out_specs=(sess.replicate(), sess.replicate()))
    return fn, (sess.scatter(jnp.asarray(x)), sess.scatter(jnp.asarray(y)),
                params0)


def _serve_classify():
    from harp_tpu.models import nn
    from harp_tpu.serve import endpoints as serve_ep

    sess = _session()
    model = nn.MLPClassifier(sess, nn.NNConfig(layers=(8,), num_classes=3))
    model.params = nn.init_params((12, 8, 3), seed=0)
    ep = serve_ep.classify_from_nn(sess, model, name="nn")
    x = _rng().normal(size=(ep.bucket_sizes[0], 12)).astype("float32")
    fn, args, _n, _bucket = ep.prepared(x)
    return fn, args


def _serve_topk():
    from harp_tpu.serve import endpoints as serve_ep

    sess = _session()
    rng = _rng()
    uf = rng.normal(size=(64, 8)).astype("float32")
    items = rng.normal(size=(32, 8)).astype("float32")
    ep = serve_ep.TopKEndpoint(sess, "mf", uf, items, k=4)
    ids = rng.integers(0, 64, size=ep.bucket_sizes[0])
    fn, args, _n, _bucket = ep.prepared(ids)
    return fn, args


def _serve_topk_rebalanced():
    from harp_tpu.serve import endpoints as serve_ep

    sess = _session()
    rng = _rng()
    uf = rng.normal(size=(64, 8)).astype("float32")
    items = rng.normal(size=(32, 8)).astype("float32")
    ep = serve_ep.TopKEndpoint(sess, "mf", uf, items, k=4)
    ep.rebalance(1)       # owner-map routed dispatch (ISSUE 11 rebalance)
    ids = rng.integers(0, 64, size=ep.bucket_sizes[0])
    fn, args, _n, _bucket = ep.prepared(ids)
    return fn, args


def _serve_topk_int8():
    """The QUANTIZED serving dispatch (ISSUE 17): same 3 all_to_alls +
    1 psum as serve_topk_mf, but the route-back all_to_all carries packed
    int8 factor rows (r+4 bytes/row instead of 4r f32 bytes) — the pinned
    byte row sits strictly below the f32 twin's, so a silent f32 revert
    grows bytes at the same counts and fails JL203."""
    from harp_tpu.serve import endpoints as serve_ep

    sess = _session()
    rng = _rng()
    uf = rng.normal(size=(64, 8)).astype("float32")
    items = rng.normal(size=(32, 8)).astype("float32")
    ep = serve_ep.TopKEndpoint(sess, "mf", uf, items, k=4, quant="int8")
    ids = rng.integers(0, 64, size=ep.bucket_sizes[0])
    fn, args, _n, _bucket = ep.prepared(ids)
    return fn, args


def _multiclass_svm_pairs():
    """The multiclass one-vs-one TRAINING program: all pair machines in one
    vmapped rotation-blocked kernel-dual program (KernelSVM.
    _fit_padded_pairs builds exactly this spmd: pairs on the vmap batch
    axis, rows sharded over workers on axis 1) at a 3-class tier-1 shape
    — the r8 dryrun leg's step program, now budget-pinned (ISSUE 14
    satellite)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harp_tpu.models import svm as svm_mod

    sess = _session()
    cfg = svm_mod.KernelSVMConfig(kernel="rbf", iterations=3, power_iters=2)
    p, n_pad, d = 3, 64, 6              # 3 classes -> 3 pair machines
    fn = sess.spmd(
        jax.vmap(lambda a, t, c: svm_mod._train_kernel_dual(a, t, c, cfg)),
        in_specs=(sess.shard(1),) * 3,
        out_specs=(sess.shard(1), sess.replicate(), sess.replicate()))
    rng = _rng()
    xb = rng.normal(size=(p, n_pad, d)).astype("float32")
    yb = np.sign(rng.normal(size=(p, n_pad))).astype("float32")
    cb = np.full((p, n_pad), cfg.c, "float32")
    return fn, (sess.scatter(jnp.asarray(xb), axis=1),
                sess.scatter(jnp.asarray(yb), axis=1),
                sess.scatter(jnp.asarray(cb), axis=1))


def _distributed_sort():
    """The r10 sort/quantiles dryrun leg's heavy program: the distributed
    odd-even block sort (sharded output assembled by fetch) at the tier-1
    shape — its ppermute ladder is exactly the cross-worker traffic the
    gang rows exist to price."""
    import jax.numpy as jnp

    from harp_tpu.models import stats as stats_mod
    from harp_tpu.ops import linalg

    sess = _session()
    s = stats_mod.Sorting(sess)
    fn = s._compile("sort", lambda a: linalg.distributed_sort(a), 0,
                    extra_sharded_out=1)
    x = _rng().standard_normal((128, 6)).astype("float32")
    return fn, (sess.scatter(jnp.asarray(x)),)


def _csr_cov():
    """The r10 CSR covariance/PCA dryrun leg's step program: the blocked
    densify-GEMM gram from CSR input over the mesh (sparse_gram_stats) —
    CSRPCA rides the same program plus a replicated eigensolve."""
    from harp_tpu.io import datagen
    from harp_tpu.models import sparse as sp

    sess = _session()
    n, dim = 128, 12
    rows, cols, vals = datagen.sparse_points(n, dim, 0.2, seed=9)
    cov = sp.CSRCovariance(sess)
    idx, val, mask, real = cov._layout(rows, cols, vals, n, dim)
    cov._stats(rows, cols, vals, n, dim)     # populate the compile cache
    fn = cov._fns[(idx.shape, dim)]
    return fn, (sess.scatter(idx), sess.scatter(val), sess.scatter(mask),
                sess.scatter(real))


def _kmeans_fileload():
    """The r11 file-load dryrun leg: K-means fed from part-files on disk
    through the io/loaders pipeline (list_files glob -> split -> threaded
    CSV load -> scatter). Pinning it as its own gang row asserts the
    ingestion path feeds the SAME step program as the in-memory twin —
    bytes identical, or the leg's bitwise-parity promise broke."""
    import shutil
    import tempfile

    import numpy as np

    from harp_tpu.io import datagen, loaders
    from harp_tpu.models import kmeans as km

    sess = _session()
    io_dir = tempfile.mkdtemp(prefix="harp-lint-io-")
    try:
        pts = datagen.dense_points(64, 16, seed=11, num_clusters=8)
        for i, part in enumerate(np.array_split(pts, 4)):
            np.savetxt(os.path.join(io_dir, f"part-{i:05d}.csv"), part,
                       delimiter=",", fmt="%.8e")
        paths = loaders.list_files(os.path.join(io_dir, "part-*"))
        splits = loaders.split_files(paths, 2)
        loaded = loaders.load_dense_csv([p for s in splits for p in s])
        loaded = loaders.truncate_to_workers(loaded, NUM_WORKERS)
    finally:
        shutil.rmtree(io_dir, ignore_errors=True)
    model = km.KMeans(sess, km.KMeansConfig(8, 16, iterations=2,
                                            comm="regroupallgather"))
    p, c = model.prepare(loaded, loaded[:8].copy())
    return model._fit, (p, c)


def _reshard(schedule: str):
    def build():
        import numpy as np

        from harp_tpu.collectives import reshard as rs
        from harp_tpu.models.sgd_mf import identity_assign, serpentine_assign

        sess = _session()
        rng = _rng()
        # a W=4 checkpointed factor table re-sharded onto the 8-worker
        # tracing mesh: serpentine old maps, identity new maps, 97 valid
        # rows (prime — the padded-slot edge is in the traced program) and
        # a 512 B chunk budget so the schedule runs MULTIPLE rounds: the
        # pinned bytes-per-step row IS the per-round foreign footprint,
        # which a schedule degrading toward a full gather would grow.
        n, r = 97, 8
        old_world, old_rpb, new_rpb = 4, 28, 16
        old = rs.block_layout(
            serpentine_assign(rng.integers(1, 9, n), old_world), old_rpb,
            old_world)
        new = rs.block_layout(identity_assign(n, NUM_WORKERS), new_rpb,
                              NUM_WORKERS)
        saved = rng.standard_normal(
            (old_world * old_rpb, r)).astype("float32")
        fill = sess.scatter(np.zeros((NUM_WORKERS * new_rpb, r),
                                     np.float32))
        plan = rs.plan_factor_reshard(old, old_world, new, NUM_WORKERS, n,
                                      r * 4, chunk_bytes=512,
                                      schedule=schedule)
        return rs.prepare_reshard(sess, saved, plan, fill)

    return build


def _ingest_coo_regroup():
    """r19 (ISSUE 18): the streaming-ingestion COO regroup step program
    (io/pipeline.regroup_coo_device) — parsed nonzeros routed to their
    row-block owner by the SAME bounded all_to_all schedule as the reshard
    engine, packed as 20 B (row i64, col i64, val f32) records.  A 512 B
    chunk budget at the tier-1 shape keeps multiple rounds in the traced
    program, so the pinned bytes-per-step row IS the per-round foreign
    footprint: a regroup degrading toward a whole-table gather grows it
    and fails JL203."""
    import numpy as np

    from harp_tpu.collectives import reshard as rs
    from harp_tpu.io import pipeline as pl

    sess = _session()
    rng = _rng()
    n, num_rows = 300, 97
    rows = rng.integers(0, num_rows, n).astype(np.int64)
    cols = rng.integers(0, 64, n).astype(np.int64)
    vals = rng.standard_normal(n).astype(np.float32)
    plan, counts, cap = rs.plan_coo_regroup(rows, num_rows, NUM_WORKERS,
                                            chunk_bytes=512)
    rec = pl.pack_coo(rows, cols, vals)
    fill = sess.scatter(np.zeros((NUM_WORKERS * cap, 5), np.int32))
    return rs.prepare_reshard(sess, rec, plan, fill)


# Registry: target name -> builder returning (traceable callable, args).
# Names are the manifest keys — renaming one is a manifest change.
# The *_int8/*_bf16 rows pin the QUANTIZED step programs: their byte rows
# sit far below the f32 twins', so a quantized path silently reverting to
# f32 (same collective counts, 2-4x the operand bytes) fails JL203 exactly
# like count drift fails JL201.
# The *_fused rows (r10) pin the fused ring-DMA step programs: the wt/H
# rotation hops trace as the tagged `fused_dma` kind (checkers_jaxpr
# FUSED_HOP_PREFIX) with the SAME bytes the f32 ppermute moved — a fused
# schedule silently reverting to bare ppermute swaps those bytes back
# between kinds and fails the gate. lda_cgs_quantwt_int8 pins the
# satellite quantized wt-block rotation (ISSUE 9): its ppermute bytes sit
# far below lda_cgs's because the (vpb, K) block ships int8+scales.
# The serve_* rows (r11) pin the ONLINE-SERVING dispatch programs:
# serve_classify_nn must stay at ZERO collectives (replicated params,
# sharded query batch — a psum/all_gather sneaking into the resident
# predict dispatch fails JL201 loudly), and serve_topk_mf must stay at
# exactly the 3 all_to_alls of the keyval DistributedKV lookup
# (bucket_route payload + mask, route_back) — the parameter-server pull
# path the top-k endpoint serves from. Retrace policing is the other half:
# the endpoints hold one compiled fn per (model, batch-bucket) in the
# JL103-clean `self._fns[bucket]` cache, and tests/test_serve.py asserts
# exactly one trace per bucket under live traffic.
TARGETS: Dict[str, Callable[[], Tuple[Callable, tuple]]] = {
    "kmeans_regroupallgather": _kmeans("regroupallgather"),
    "kmeans_allreduce": _kmeans("allreduce"),
    "kmeans_pushpull": _kmeans("pushpull"),
    "kmeans_bcastreduce": _kmeans("bcastreduce"),
    "kmeans_rotation": _kmeans("rotation"),
    "kmeans_allreduce_int8": _kmeans("allreduce", quant="int8"),
    "kmeans_regroupallgather_bf16": _kmeans("regroupallgather",
                                            quant="bf16"),
    "lda_cgs": _lda(),
    "lda_cgs_fused": _lda(fused_dma=True),
    "lda_cgs_quantwt_int8": _lda(quant="int8", quant_wt=True),
    "lda_cgs_subblock128": _lda_subblock,
    "sgd_mf_dense": _sgd_mf(),
    "sgd_mf_dense_int8": _sgd_mf(quant="int8"),
    "sgd_mf_dense_fused": _sgd_mf(fused_dma=True),
    "als_explicit": _als,
    "pagerank": _pagerank,
    "nn_mlp": _nn,
    "serve_classify_nn": _serve_classify,
    "serve_topk_mf": _serve_topk,
    # r12 (ISSUE 11): the on-device reshard step programs. The a2a row pins
    # ONE all_to_all per round whose operand bytes ARE the per-round
    # foreign-row budget (chunk_bytes at the traced shape) — a schedule
    # silently degrading toward a full gather (bigger rounds, or a
    # fall-back all_gather) changes kinds/bytes and fails JL201/JL203. The
    # ring row pins the per-shift ppermute schedule (rides lax_ops.rotate,
    # so DCN chunking composes). serve_topk_mf_rebalanced pins the
    # owner-map-routed serving dispatch a rebalance() switches to: the
    # SAME 3 all_to_alls as serve_topk_mf — rebalancing moves shards, it
    # must never add a collective to the request path.
    "reshard_factor_a2a": _reshard("alltoall"),
    "reshard_factor_ring": _reshard("ring"),
    "serve_topk_mf_rebalanced": _serve_topk_rebalanced,
    # r17 (ISSUE 17): the int8 serving dispatch — the quantized twin of
    # serve_topk_mf (same collective counts, packed int8 route-back), the
    # budget row that makes a silent f32 revert on the REQUEST path as
    # loud as one on a training path.
    "serve_topk_mf_int8": _serve_topk_int8,
    # r19 (ISSUE 18): the streaming-ingestion distributed COO regroup — the
    # per-round all_to_all operand bytes ARE the ≤ chunk_bytes budget
    # (8 peers x 3 records x 20 B = 480 B at the traced 512 B budget); a
    # regroup silently reverting to a whole-table host/device gather
    # changes kinds or grows bytes and fails JL201/JL203.
    "ingest_coo_regroup": _ingest_coo_regroup,
    # ISSUE 34: WDA-SMACOF. A CG step is one all_gather of the direction
    # and two psums (p'Vp; the residual's squared norm and its sum, folded);
    # an iteration adds the stress psum, the warm start's two psums and the
    # all_gather of the new embedding. A third psum in the step, or the
    # matrices' rows gathered, changes kinds or bytes and fails JL201/JL203.
    "wdamds": _wdamds,
}


# -- gang-mode targets (ISSUE 13 tentpole, the carried "jaxlint multi-host
# budgets" ROADMAP item) ----------------------------------------------------
#
# A gang-mode target is a `dryrun_multichip` step program traced on the
# SAME 8-worker tracing mesh but with a declared multi-process topology:
# ``processes`` hosts x ``devices_per_process`` local devices, the workers
# axis laid out contiguously per process (exactly how
# ``parallel.distributed.initialize`` + ``make_mesh`` place a real gang —
# mp_smoke's 2x4 layout). The program is SPMD, so every process traces the
# SAME jaxpr; what differs per process is the SHARD it owns and which hops
# cross the data-center network instead of on-pod ICI. The manifest row
# therefore pins, besides counts/bytes:
#
# * ``per_process_shard_shapes`` — the per-process block of every program
#   input (a replicated dim stays global; a workers-sharded dim is the
#   global extent over ``processes``). A drifted shard shape means the
#   partitioner changed what each HOST holds — a resharding contract break
#   (arXiv:2112.01075 treats the redistribution layout as first-class),
#   JL201.
# * ``bytes_by_link`` — ``bytes_by_kind`` split DCN vs ICI with the
#   ring-edge/peer model in checkers_jaxpr.split_bytes_by_link, gated on
#   ``mesh.axis_link_class(WORKERS)`` (gang launchers hint the workers
#   axis "dcn" at bootstrap; the DrJAX-style multi-mesh programs of
#   arXiv:2403.07128 make that DCN/ICI split first-class). Growing DCN
#   bytes at fixed counts is exactly the cross-pod regression the
#   single-process rows cannot see, JL203.
#
# The workloads are the dryrun_multichip gang's own exercises (mp_smoke):
# K-means over both parallelism families, SGD-MF, and LDA.

GANG_PROCESSES = 2
GANG_DEVICES_PER_PROCESS = 4     # 2 x 4 = NUM_WORKERS, mp_smoke's layout

GANG_TARGETS: Dict[str, Callable[[], Tuple[Callable, tuple]]] = {
    "gang2x4_kmeans_regroupallgather": _kmeans("regroupallgather"),
    "gang2x4_kmeans_rotation": _kmeans("rotation"),
    "gang2x4_sgd_mf_dense": _sgd_mf(),
    "gang2x4_lda_cgs": _lda(),
    # ISSUE 14 satellite — the dryrun legs that landed without gang rows
    # (ROADMAP: "new gang workloads should add gang rows as they land"):
    # multiclass one-vs-one SVM (r8), distributed sort (the r10
    # sort/quantiles leg's comm-heavy half), CSR covariance (the r10
    # cov/PCA leg's step program), and the file-load leg's K-means step
    # (pins that the ingestion pipeline feeds a byte-identical program).
    "gang2x4_multiclass_svm_pairs": _multiclass_svm_pairs,
    "gang2x4_distributed_sort": _distributed_sort,
    "gang2x4_csr_cov": _csr_cov,
    "gang2x4_kmeans_fileload": _kmeans_fileload,
}
