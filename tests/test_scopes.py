"""Device scopes (ISSUE 25): the two step programs, compiled for a described
``v5e:2x2`` at the benchmark cells' widths, carry a listed scope on every
kernel of their loop bodies; ``scope_map`` reads the compiled text and
``device_time_by_scope`` a recorded v5e trace.

The topology is described inside a module fixture and nowhere else (the
on-chip-measurement guide, section 2): only the worker that is handed this
file loads the TPU's library. Nothing runs: a compile says nothing about time.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from harp_tpu.models import als, ccd, em, kmeans, mds, sgd_mf
from harp_tpu.session import HarpSession
from harp_tpu.telemetry import scopes

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark",
                     "data", "kmeans_v5e_1chip.xplane.pb")
# what shows as a device event of its own and takes the time
KERNELS = {"fusion", "convolution", "copy", "custom-call",
           "dynamic-update-slice", "all-reduce", "all-gather",
           "reduce-scatter", "collective-permute", "all-to-all"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep it off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shaped(sess, shape, dtype, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(sess.mesh, spec))


KMEANS_ROWS = 65536     # a worker's points in the K-means step compiled here


def _kmeans_text(topo, workers: int, ambient="highest", fused: bool = False,
                 rows: int = KMEANS_ROWS) -> str:
    """The K-means step at the cell's widths (k 100, d 100 lane-padded to
    128, ``highest`` products as the cell sets them), the point count cut.
    ``fused``: with the E-step kernel the dispatch picks on the chip (the
    predicate asks ``jax`` for its backend, which is the CPU here)."""
    sess = HarpSession(num_workers=workers, devices=topo.devices[:workers])
    model = kmeans.KMeans(sess, kmeans.KMeansConfig(
        num_centroids=100, dim=100, iterations=5))
    points = _shaped(sess, (rows * workers, 128), jnp.float32, sess.shard())
    centroids = _shaped(sess, (100, 100), jnp.float32, sess.replicate())
    with pytest.MonkeyPatch.context() as patch:
        if fused:
            patch.setattr(kmeans, "_fused_estep", lambda *a: (True, False))
        with jax.default_matmul_precision(ambient):
            return model._fit.lower(points, centroids).compile().as_text()


def _sgdmf_text(topo, workers: int, fused: bool = False,
                ring: bool = False) -> str:
    """The dense SGD-MF step at the cells' widths as the layout stores them
    (rank 100 as 104, ML-10M's or ML-20M's columns per block padded to 256),
    the stripes cut to 128 rows. ``fused``: the program the chip
    runs, with the fused hop kernel (the dispatch asks ``jax`` for its
    backend, which is the CPU here); ``ring``: with the hop's H block sent
    from inside that kernel, the wire the chip picks on a ring of four
    (``fused_dma`` left to the program)."""
    from harp_tpu.ops import pallas_kernels, ring_dma

    sess = HarpSession(num_workers=workers, devices=topo.devices[:workers])
    model = sgd_mf.SGDMF(sess, sgd_mf.SGDMFConfig(
        rank=100, lam=0.05, lr=1e-4, minibatches_per_hop=8, epochs=5))
    g, n_blocks = model._dense_geometry(
        8 * 128 * workers, 10681 if workers == 1 else 26744)
    assert (g.s_rows, g.rank_store, n_blocks) == (128, 104, workers)
    rows, cpb, k = g.rpw_store, g.cpb_store, g.rank_store
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pallas_kernels, "use_dense_mf_pallas",
                      lambda *shape: fused)
        patch.setattr(ring_dma, "use_ring_dma", lambda: ring)
        key = model._program("dense", g.nmb, 5, g)
    shard = sess.shard()
    args = (_shaped(sess, (workers, workers, rows, cpb), jnp.bfloat16, shard),
            _shaped(sess, (workers, workers, rows), jnp.float32, shard),
            _shaped(sess, (workers, workers, g.nmb, cpb), jnp.float32, shard),
            _shaped(sess, (workers * rows, k), jnp.float32, shard),
            _shaped(sess, (workers * cpb, k), jnp.float32, shard))
    return model._compiled[key].lower(*args).compile().as_text()


ALS_SHAPE = (71_567, 10_681, 100)     # the cell als-k100.ml10m, whole


def _als_step(topo):
    """The compiled dense ALS iteration at the cell's full shape on one
    described chip, with the solve kernel ``solver="auto"`` picks there (the
    dispatch asks ``jax`` for its backend, which is the CPU here)."""
    from harp_tpu.ops import pallas_kernels

    m, n, k = ALS_SHAPE
    sess = HarpSession(num_workers=1, devices=topo.devices[:1])
    model = als.ALS(sess, als.ALSConfig(
        rank=k, lam=0.05, alpha=40.0, iterations=1, layout="dense"))
    key = model._dense_program(m, n)
    args = (_shaped(sess, (m, n), jnp.bfloat16, sess.shard()),
            _shaped(sess, (n, m), jnp.bfloat16, sess.shard()),
            _shaped(sess, (m, k), jnp.float32, sess.replicate()),
            _shaped(sess, (n, k), jnp.float32, sess.replicate()))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pallas_kernels, "use_spd_solve_pallas", lambda k: True)
        return model._fns[key].lower(*args).compile()


def _ccd_step(topo, workers: int = 1):
    """The compiled CCD++ outer iteration at the cell ccd-k100.ml10m's full
    shape, with the sweep kernel the dispatch picks on the chip (the
    predicate and the sides' ``interpret`` ask ``jax`` for its backend, which
    is the CPU here)."""
    from harp_tpu.ops import ccd_sweep

    m, n, k = ALS_SHAPE
    sess = HarpSession(num_workers=workers, devices=topo.devices[:workers])
    model = ccd.CCD(sess, ccd.CCDConfig(rank=k, lam=0.05, outer_iterations=1))
    u_rpw, i_rpw = -(-m // workers), -(-n // workers)
    u_pad, i_pad = workers * u_rpw, workers * i_rpw
    args = (_shaped(sess, (u_pad, i_pad), jnp.bfloat16, sess.shard()),
            _shaped(sess, (i_pad, u_pad), jnp.bfloat16, sess.shard()),
            _shaped(sess, (u_pad, k), jnp.float32, sess.replicate()),
            _shaped(sess, (i_pad, k), jnp.float32, sess.replicate()))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ccd_sweep, "use_ccd_sweep_pallas",
                      lambda rows, cols, kp: True)
        real = ccd._geometry
        patch.setattr(ccd, "_geometry", lambda *a: tuple(
            dataclasses.replace(s, interpret=False) for s in real(*a)))
        key = model._program(u_rpw, i_rpw)
        return key, model._fns[key].lower(*args).compile()


MDS_POINTS = 32_768     # the cell wdamds-d3.clusters-32k


def _mds_step(topo, workers: int = 1):
    """The compiled call of 10 WDA-SMACOF iterations at the cell
    wdamds-d3.clusters-32k's full shape (float32 distances, bfloat16
    weights, target dimension 3), with the two kernels the dispatch picks on
    the chip (the predicate and the geometry's ``interpret`` ask ``jax`` for
    its backend, which is the CPU here)."""
    from harp_tpu.ops import mds_kernels

    n = MDS_POINTS
    sess = HarpSession(num_workers=workers, devices=topo.devices[:workers])
    model = mds.WDAMDS(sess, mds.MDSConfig(dim=3, iterations=10))
    args = (_shaped(sess, (n, n), jnp.float32, sess.shard()),
            _shaped(sess, (n, n), jnp.bfloat16, sess.shard()),
            _shaped(sess, (n,), jnp.float32, sess.shard()),
            _shaped(sess, (2,), jnp.float32, sess.replicate()),
            _shaped(sess, (mds_kernels.DIM_PAD, n), jnp.float32,
                    sess.replicate()),
            _shaped(sess, (), jnp.int32, sess.replicate()))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mds_kernels, "use_mds_pallas", lambda *a: True)
        real = mds._geometry
        patch.setattr(mds, "_geometry", lambda *a: dataclasses.replace(
            real(*a), interpret=False))
        key = model._program(n, jnp.bfloat16)
        return key, model._fns[key].lower(*args).compile()


EM_POINTS = 6_000_000   # the cell emgmm-k100d100.aniso-6m


def _em_step(topo, rows: int = EM_POINTS):
    """The compiled call of two EM iterations (one would leave no loop) at
    the cell emgmm-k100d100.aniso-6m's full shape (K = D = 100, the points
    stored in 128 lanes), with the fused E-step the dispatch picks on the
    chip (the predicate and the geometry's ``interpret`` ask ``jax`` for its
    backend, which is the CPU here)."""
    from harp_tpu.ops import em_kernels

    sess = HarpSession(num_workers=1, devices=topo.devices[:1])
    model = em.EMGMM(sess, em.EMConfig(num_components=100, iterations=2))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(em_kernels, "use_em_estep_pallas", lambda *a: True)
        geom = dataclasses.replace(em._geometry(rows, 100, 100),
                                   interpret=False)
    key = model._program(geom, 2)
    args = (_shaped(sess, (rows, geom.d_store), jnp.float32, sess.shard()),
            _shaped(sess, (100,), jnp.float32, sess.replicate()),
            _shaped(sess, (100, 100), jnp.float32, sess.replicate()),
            _shaped(sess, (100, 100, 100), jnp.float32, sess.replicate()))
    return model._fns[key].lower(*args).compile()


LDA_TOKENS = 99_500_000  # the cell lda-k1000.nytimes-100m


def _lda_step(topo):
    """The compiled call of two sweeps at the cell lda-k1000.nytimes-100m's
    full shape (K = 1,000, 102,660 words, 299,752 documents in 32 groups,
    the stream's tiles as the cell's corpus lays them out, within a few
    per cent), with the fused sampler the dispatch picks on the chip."""
    from harp_tpu.models import lda
    from harp_tpu.ops import lda_kernels as lk

    sess = HarpSession(num_workers=1, devices=topo.devices[:1])
    groups, docs, dg = 32, 299_752, 9_528
    max_tiles = 4_100
    tiles = groups * max_tiles
    geom = lda._Stream(
        workers=1, k=1000, vocab=102_660, alpha=0.05, beta=0.01,
        words_store=102_784, tokens=LDA_TOKENS, groups=groups,
        slots=tiles * lk.TILE, tiles=tiles, max_tiles=max_tiles,
        doc_rows=docs + dg, dg=dg, doc_const=0.0, kernel=True)
    model = lda.LDA(sess, lda.LDAConfig(num_topics=1000, vocab=102_660,
                                        layout="stream",
                                        groups_per_sweep=groups))
    key = model._stream_program(geom, 2)
    tile = (lk.SUBLANES, lk.LANES)
    sh, rep = sess.shard(), sess.replicate()
    args = ((_shaped(sess, (geom.slots,), jnp.int32, sh),) * 3
            + (_shaped(sess, (tiles,), jnp.int32, sh),) * 2
            + (_shaped(sess, (groups, 4), jnp.int32, sh),
               _shaped(sess, (2,), jnp.uint32, rep),
               _shaped(sess, (geom.slots,), jnp.int32, sh),
               _shaped(sess, (geom.words_store, *tile), jnp.int32, rep),
               _shaped(sess, (geom.doc_rows, *tile), jnp.int32, sh),
               _shaped(sess, tile, jnp.int32, rep),
               _shaped(sess, (), jnp.int32, rep)))
    return model._fns[key].lower(*args).compile()


def _loop_lines(text: str):
    """The instruction lines of every ``while`` body of the compiled text,
    nested loops included."""
    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    out, computation = [], None
    for line in text.splitlines():
        if scopes._INSTRUCTION.match(line) is None:
            c = scopes._COMPUTATION.match(line)
            computation = c.group(1) if c else computation
        elif computation in bodies:
            out.append(line)
    return out


def _loop_kernels(text: str):
    """``(instruction, opcode)`` of the kernels that stand directly in a
    ``while`` body of the compiled text."""
    out = []
    for line in _loop_lines(text):
        name, opcode = scopes._instruction(line.strip())
        opcode = re.sub(r"-(start|done)$", "", opcode)
        if opcode in KERNELS:
            out.append((name, opcode))
    return out


def _bf16_shape(shape) -> str:
    return "bf16[" + ",".join(str(n) for n in shape) + "]"


def _yields(lines):
    """``(name, opcode, result)`` of the instructions among ``lines`` that
    make a value: what only renames a buffer (a parameter, a tuple or its
    element, a loop's carry, a bitcast) yields nothing new. ``result`` is
    the text of the result's shapes."""
    for line in lines:
        if scopes._INSTRUCTION.match(line) is None:
            continue
        name, opcode = scopes._instruction(line.strip())
        if opcode in ("parameter", "get-tuple-element", "bitcast", "tuple",
                      "while"):
            continue
        yield name, opcode, line.split(" = ", 1)[1].split(opcode + "(", 1)[0]


def _block_sized_bf16(lines, cells: int):
    """The instructions among ``lines`` that yield a bf16 array of at least
    ``cells`` elements: a copy of a slab's block, by whatever name (``(name,
    opcode, shape)`` each)."""
    return [(name, opcode, dims) for name, opcode, result in _yields(lines)
            for dims in re.findall(r"bf16\[([\d,]+)\]", result)
            if np.prod([int(n) for n in dims.split(",")]) >= cells]


PROGRAMS = {
    "kmeans-1": (lambda t: _kmeans_text(t, 1),
                 {"kmeans.norms", "kmeans.scores", "kmeans.stats",
                  "kmeans.update"}),
    "kmeans-4": (lambda t: _kmeans_text(t, 4),
                 {"kmeans.norms", "kmeans.scores", "kmeans.stats",
                  "kmeans.update", "lax.allgather"}),
    "sgdmf-1": (lambda t: _sgdmf_text(t, 1),
                {"sgdmf.stripes", "sgdmf.rmse", "rotation.hop"}),
    "sgdmf-4": (lambda t: _sgdmf_text(t, 4),
                {"sgdmf.select", "sgdmf.stripes", "sgdmf.rmse",
                 "rotation.hop"}),
    "sgdmf-1-fused": (lambda t: _sgdmf_text(t, 1, fused=True),
                      {"sgdmf.stripes", "sgdmf.rmse", "rotation.hop"}),
    "sgdmf-4-fused": (lambda t: _sgdmf_text(t, 4, fused=True),
                      {"sgdmf.select", "sgdmf.stripes", "sgdmf.rmse",
                       "rotation.hop"}),
    # the same with the kernel sending its H block: no hop outside it
    "sgdmf-4-ring-fused": (lambda t: _sgdmf_text(t, 4, fused=True, ring=True),
                           {"sgdmf.select", "sgdmf.stripes", "sgdmf.rmse"}),
    # the ALS iteration at the cell's full shape; its one kernel is the solve
    "als-1-fused": (lambda t: _als_step(t).as_text(),
                    {"als.outer", "als.gram", "als.rhs", "als.solve",
                     "als.monitor"}),
    # the CCD++ outer iteration at the cell's full shape: two sweep kernels
    # a feature and round, on one chip and on four. There the compiler makes
    # the allgather of a column an all-reduce of its own, with no op_name:
    # it takes the scope of the pad that reads it, ccd.column
    "ccd-1-fused": (lambda t: _ccd_step(t)[1].as_text(),
                    {"ccd.sweep", "ccd.column", "ccd.monitor"}),
    "ccd-4-fused": (lambda t: _ccd_step(t, 4)[1].as_text(),
                    {"ccd.sweep", "ccd.column", "ccd.monitor"}),
    # the WDA-SMACOF call at the cell's full shape: the B(X)X kernel once an
    # iteration, the matvec kernel for the warm start's residual and in the
    # CG's loop, on one chip and on four
    "mds-1-fused": (lambda t: _mds_step(t)[1].as_text(),
                    {"mds.anneal", "mds.bc", "mds.cg"}),
    "mds-4-fused": (lambda t: _mds_step(t, 4)[1].as_text(),
                    {"mds.anneal", "mds.bc", "mds.cg", "lax.allgather"}),
    # EM iterations at the cell's full shape: the factorization, the fused
    # E-step, the M-step
    "em-1-fused": (lambda t: _em_step(t).as_text(),
                   {"em.factor", "em.estep", "em.update"}),
    # LDA sweeps at the cell's full shape: the fused sampler a group, the
    # new topics into z, the quality a sweep
    "lda-1-fused": (lambda t: _lda_step(t).as_text(),
                    {"lda.sample", "lda.counts", "lda.quality"}),
}


@pytest.fixture(scope="module")
def compiled(topo, no_compile_cache):
    texts = {}

    def get(program: str) -> str:
        if program not in texts:
            texts[program] = PROGRAMS[program][0](topo)
        return texts[program]

    return get


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_kernel_of_the_loop_bodies_has_a_listed_scope(compiled, program):
    text = compiled(program)
    mapped = scopes.scope_map(text)
    kernels = _loop_kernels(text)
    assert len(kernels) >= 5, kernels
    bare = [(name, op) for name, op in kernels
            if mapped[name] not in scopes.SCOPES]
    assert not bare, bare
    if program.endswith("-fused"):
        # the hop is the one kernel; what stands around it (the transposes
        # of the H block the rotator ships, the count broadcasts) is fusions:
        # W is carried in the kernel's form and nothing of its size stands
        # in the loops (test_the_hop_loops_hold_no_w_sized_operand_...)
        assert "tpu_custom_call" in text
        assert {op for _, op in kernels} >= {"fusion", "custom-call"}
    else:
        assert "tpu_custom_call" not in text
        assert {op for _, op in kernels} >= {"fusion", "dynamic-update-slice"}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_each_scope_the_program_reaches_is_present(compiled, program):
    text = compiled(program)
    loops = {scopes.scope_map(text)[name] for name, _ in _loop_kernels(text)}
    everywhere = set(scopes.scope_map(text).values())
    reached = PROGRAMS[program][1]
    # the hoisted norms run once, before the loop; the rest inside it
    assert reached - {"kmeans.norms"} <= loops, (reached, loops)
    assert reached <= everywhere
    if program.startswith("sgdmf"):
        assert not any(s and s.startswith("kmeans") for s in everywhere)
    # a ring of one picks its single block by a static index: no select
    if program.startswith("sgdmf-1"):
        assert "sgdmf.select" not in loops


def test_the_kmeans_stats_are_one_product_that_counts(compiled):
    """The stats product counts through the spare lane, so nothing else under
    ``kmeans.stats`` reads a row-sized operand and no second reduction of
    the one-hot is left. Both products run at the cell's ambient ``highest``:
    the program pins no precision (the TPU backend runs a
    ``{default,highest}`` pair no faster: PERF.md, Findings, PR 32)."""
    text = compiled("kmeans-1")
    mapped = scopes.scope_map(text)
    products = {re.search(r'op_name="[^"]*?(kmeans\.\w+)/dot_general"',
                          line).group(1):
                re.search(r"operand_precision=\{(\w+),(\w+)\}", line).groups()
                for line in text.splitlines() if " convolution(" in line}
    assert products == {"kmeans.scores": ("highest", "highest"),
                        "kmeans.stats": ("highest", "highest")}
    assert "kmeans.stats/reduce_sum" not in text
    # operands are names in the compiled text; what a name yields stands on
    # its own line, before its opcode
    rows = re.compile(r"\[%d[,\]]" % KMEANS_ROWS)
    row_sized = {scopes._instruction(line.strip())[0]
                 for line in text.splitlines()
                 if scopes._INSTRUCTION.match(line)
                 and rows.search(line.split(" = ", 1)[1].split("(", 1)[0])}
    reads_rows = [name for line in _loop_lines(text)
                  for name, opcode in [scopes._instruction(line.strip())]
                  if opcode in KERNELS and mapped[name] == "kmeans.stats"
                  and row_sized & set(re.findall(
                      r"%([\w.\-]+)", line.split(opcode + "(", 1)[1]))]
    assert len(reads_rows) == 1, reads_rows


@pytest.mark.parametrize("workers, rows, ambient, passes", [
    (1, 8_000_000, "highest", 6),           # the cell kmeans-d100.overlap-8m
    (4, KMEANS_ROWS, "highest", 6),
    (1, KMEANS_ROWS, None, 1)])
def test_the_fused_estep_is_the_loops_one_pass_over_the_points(
        topo, no_compile_cache, workers, rows, ambient, passes):
    """With the predicate on, an iteration reads the points in ONE kernel,
    ``kmeans_estep`` under ``kmeans.estep``: no score or stats convolution
    and no hoisted norms are left, and the centroids reach the kernel as the
    bfloat16 passes the ambient precision states, side by side (six at
    ``highest``, bf16_6x's: the passes are the kernel's own)."""
    text = _kmeans_text(topo, workers, ambient, fused=True, rows=rows)
    mapped = scopes.scope_map(text)
    kernels = _loop_kernels(text)
    calls = [name for name, opcode in kernels if opcode == "custom-call"]
    assert len(calls) == 1 and calls[0].startswith("kmeans_estep"), calls
    assert mapped[calls[0]] == "kmeans.estep"
    call = next(line for line in _loop_lines(text)
                if scopes._instruction(line.strip())[0] == calls[0])
    assert f"f32[{rows},128]" in call and f"bf16[128,{128 * passes}]" in call
    assert " convolution(" not in text
    everywhere = set(mapped.values())
    assert not {"kmeans.norms", "kmeans.scores"} & everywhere, everywhere
    bare = [(name, op) for name, op in kernels
            if mapped[name] not in scopes.SCOPES]
    assert not bare, bare
    # nothing row-sized but the points themselves: no N-sized temporary
    assert not re.search(r"= \w+\[%d[,\]]" % rows, "\n".join(
        line for line in text.splitlines() if " parameter(" not in line
        and "get-tuple-element(" not in line)), "an N-sized value is made"


def test_at_the_ambient_default_the_kmeans_step_asks_for_no_precision(
        topo, no_compile_cache):
    """A user who sets nothing gets the products they got: one term each."""
    text = _kmeans_text(topo, 1, ambient=None)
    assert " convolution(" in text
    assert "highest" not in text and "operand_precision" not in text


@pytest.mark.parametrize("workers, num_rows, num_cols", [
    (1, 71_567, 10_681), (4, 138_493, 26_744)])
def test_the_fused_hop_compiles_at_the_cells_stored_geometry(
        topo, no_compile_cache, workers, num_rows, num_cols):
    """Mosaic accepts the hop kernel at the MovieLens cells' full stored
    stripes and blocks, at the tile the dispatch picks and under the VMEM
    limit it is given: the estimate that picks the tile is not short."""
    from jax.sharding import SingleDeviceSharding

    from harp_tpu.ops import pallas_kernels as pk

    model = sgd_mf.SGDMF(
        HarpSession(num_workers=workers, devices=topo.devices[:workers]),
        sgd_mf.SGDMFConfig(rank=100, minibatches_per_hop=8))
    g, _ = model._dense_geometry(num_rows, num_cols)
    tile = pk.dense_mf_col_tile(g.cpb_store, g.s_store, g.rank_store)
    assert tile >= 256
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # the worker's whole slab (on four chips its four blocks: 1.9 GB
    # described, nothing allocated) and a traced block index
    hop = jax.jit(lambda slab, b, wt, ht, rc, cc: pk.dense_mf_hop_pallas(
        slab, b, wt, ht, rc, cc, 1e-4, 0.05, col_tile=tile))
    slab_shape = (workers, g.rpw_store, g.cpb_store)
    text = hop.lower(
        shaped(slab_shape, jnp.bfloat16),
        shaped((), jnp.int32),
        shaped((g.rank_store, g.rpw_store)),
        shaped((g.rank_store, g.cpb_store)),
        shaped((g.nmb, g.s_store)),
        shaped((g.nmb, g.cpb_store))).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel reads the slab itself: no copy of a block in front of it
    (call,) = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    assert _bf16_shape(slab_shape) in call.split("custom-call(")[1], call
    assert not _block_sized_bf16(text.splitlines(),
                                 g.rpw_store * g.cpb_store)


@pytest.mark.parametrize("workers", [1, 4])
def test_the_hop_loops_hold_no_w_sized_operand_but_the_kernels(compiled,
                                                               workers):
    """The dense fused program carries W as ``(K, rpw)``, the form the hop
    kernel takes and returns in place (``input_output_aliases``): inside the
    epoch and hop loops nothing but the kernel yields a float32 array of W's
    size, neither a transpose nor a layout copy nor a copy of the carry. The
    ``(rows, K)`` table is laid out once where the call takes it and once
    where it returns it."""
    text = compiled(f"sgdmf-{workers}-fused")
    rows, k = 8 * 128, 104
    w_sized = re.compile(r"f32\[(%d,%d|%d,%d)\]" % (rows, k, k, rows))

    def makes_w(lines):
        return [(name, opcode) for name, opcode, result in _yields(lines)
                if w_sized.search(result)]

    loops = _loop_lines(text)
    in_loops = makes_w(loops)
    assert [op for _, op in in_loops] == ["custom-call"], in_loops
    assert in_loops[0][0].startswith("dense_mf_hop"), in_loops
    # an asynchronous copy is one move under two names
    at_the_edges = {re.sub(r"-(start|done)", "", name) for name, _ in makes_w(
        set(text.splitlines()) - set(loops))}
    assert 1 <= len(at_the_edges) <= 2, at_the_edges


@pytest.mark.parametrize("cpb, s_rows, k, tile", [
    (10752, 8960, 104, 512),        # sgdmf-k100.ml10m, as stored
    (6912, 4352, 104, 256)])        # sgdmf-k100.ml20m-x4, a chip's
def test_the_hop_kernels_tile_at_the_cells_counts_the_stripes_operands(
        cpb, s_rows, k, tile):
    """Pure shapes: with the stripe's two bf16 operands in scratch (``2 K s``
    bytes and ``256 s``: the ``(s, K)`` form fills 128 lanes) the VMEM
    estimate still picks the tile each cell ran at before PR 37."""
    from harp_tpu.ops import pallas_kernels as pk

    assert pk.dense_mf_col_tile(cpb, s_rows, k) == tile
    without = 10 * k * cpb + 28 * k * s_rows + 6 * s_rows * tile + (4 << 20)
    assert (pk.dense_mf_hop_vmem_bytes(k, cpb, s_rows, tile) - without
            == 2 * k * s_rows + 256 * s_rows)


def test_the_als_iteration_fits_the_chip_at_the_cells_full_shape(
        topo, no_compile_cache):
    """The row-blocked iteration lowers for a v5e at 71,567 x 10,681, rank
    100: the step's scratch stays inside the budget the blocks are derived
    from, planes and scratch inside the chip, and the systems reach the
    solve kernel packed and batch-last from the product itself (no relayout
    of the normal equations stands between them, and no product has the
    10,816 rows of the full matrices)."""
    from harp_tpu.ops import pallas_kernels as pk

    step = _als_step(topo)
    stats = step.memory_analysis()
    assert stats.temp_size_in_bytes <= als.DENSE_SCRATCH_BYTES
    assert (stats.temp_size_in_bytes + stats.argument_size_in_bytes
            + stats.output_size_in_bytes) < 8e9
    text = step.as_text()
    solves = [line for line in text.splitlines()
              if "custom-call(" in line and pk.SPD_SOLVE_NAME in line]
    assert len(solves) == 2, solves                 # one a side
    mapped = scopes.scope_map(text)
    for line in solves:
        name, _ = scopes._instruction(line.strip())
        assert mapped[name] == "als.solve"
        operand = re.search(r"custom-call\(%([\w.\-]+)", line).group(1)
        assert "convolution" in operand or "pad" in operand, line
        assert "f32[5824," in line.split("custom-call(")[1], line
    products = [line for line in text.splitlines() if " convolution(" in line]
    assert any("f32[5824," in line for line in products)
    assert not [line for line in products if "10816" in line]


def test_the_ccd_iteration_fits_the_chip_at_the_cells_full_shape(
        topo, no_compile_cache):
    """The scan of 200 feature rounds lowers for a v5e at 71,567 x 10,681,
    rank 100: one sweep kernel a side, both under ``ccd.sweep``, and no
    float32 prediction plane anywhere in the step. The item plane is read
    as it lies. The user plane is not: the device keeps a (71567, 10681)
    array column-major (fewer padded cells), the kernel reads rows, and the
    compiler copies the plane row-major once a call, outside every loop
    (``PERF.md`` section 7); that copy is the step's scratch."""
    from harp_tpu.ops import ccd_sweep

    key, step = _ccd_step(topo)
    sides = key[1]
    assert [s.row_tile for s in sides] == [512, 512]
    assert [s.col_tile for s in sides] == [10_752, 14_336]
    m, n, _ = ALS_SHAPE
    stats = step.memory_analysis()
    assert stats.temp_size_in_bytes < 2 * m * n + 64 * 1024 ** 2
    assert (stats.temp_size_in_bytes + stats.argument_size_in_bytes
            + stats.output_size_in_bytes) < 5e9
    text = step.as_text()
    sweeps = [line for line in text.splitlines()
              if "custom-call(" in line and ccd_sweep.NAME in line]
    assert len(sweeps) == 2, sweeps                 # one a side
    mapped = scopes.scope_map(text)
    for line, shape in zip(sweeps, ((m, n), (n, m))):
        name, _ = scopes._instruction(line.strip())
        assert mapped[name] == "ccd.sweep"
        assert _bf16_shape(shape) in line.split("custom-call(")[1], line
    assert not _block_sized_bf16(_loop_lines(text), m * n)
    copies = _block_sized_bf16(text.splitlines(), m * n)
    assert [(op, dims) for _, op, dims in copies] == [("copy", f"{m},{n}")]
    assert not re.search(r"f32\[(71567|10681),(10681|71567)\]", text)


@pytest.mark.parametrize("workers", [1, 4])
def test_the_mds_call_fits_the_chip_at_the_cells_full_shape(
        compiled, topo, no_compile_cache, workers):
    """Ten iterations, each with its 10-step CG, lower for a v5e at 32,768
    points: ``mds_bc_stress`` under ``mds.bc``, ``mds_laplacian_matvec``
    (the warm start's and the CG loop's) under ``mds.cg``, both reading the
    matrices as they lie; the step's scratch is bounded by the tiles and
    nothing N x N is made, in any type."""
    from harp_tpu.ops import mds_kernels

    key, step = _mds_step(topo, workers)
    geom = key[1]
    n, rows = MDS_POINTS, MDS_POINTS // workers
    assert (geom.row_tile, geom.bc_col_tile, geom.mv_col_tile) == (
        512, 8192, 16384)
    stats = step.memory_analysis()
    assert stats.temp_size_in_bytes < 64 * 1024 ** 2
    assert stats.argument_size_in_bytes < 6 * rows * n + 4 * 1024 ** 2
    text = compiled(f"mds-{workers}-fused")
    mapped = scopes.scope_map(text)
    calls = {name: [line for line in text.splitlines()
                    if "custom-call(" in line and name in line]
             for name in (mds_kernels.BC_NAME, mds_kernels.MATVEC_NAME)}
    assert len(calls[mds_kernels.BC_NAME]) == 1
    assert len(calls[mds_kernels.MATVEC_NAME]) == 2
    for name, scope, operands in (
            (mds_kernels.BC_NAME, "mds.bc",
             (f"f32[{rows},{n}]", f"bf16[{rows},{n}]")),
            (mds_kernels.MATVEC_NAME, "mds.cg", (f"bf16[{rows},{n}]",))):
        for line in calls[name]:
            assert mapped[scopes._instruction(line.strip())[0]] == scope
            for operand in operands:
                assert operand in line.split("custom-call(")[1], line
    made = [line for line in text.splitlines()
            if re.search(r"= \w+\[%d,%d\]" % (rows, n), line)
            and scopes._instruction(line.strip())[1] not in (
                "parameter", "get-tuple-element", "bitcast")]
    assert not made, made
    collectives = {op for _, op in _loop_kernels(text)} & {
        "all-reduce", "all-gather"}
    assert collectives == (set() if workers == 1 else {"all-reduce",
                                                       "all-gather"})


@pytest.mark.parametrize("rows", [17_920, 10_752])
def test_the_solve_kernel_compiles_at_rank_100(topo, no_compile_cache, rows):
    """Mosaic accepts the batched Cholesky at k = 100 (stored 104) on a
    row block of the cell, its operand the 5,824 packed rows, at the lane
    tile the dispatch picks and under the VMEM limit its estimate gives."""
    from jax.sharding import SingleDeviceSharding

    from harp_tpu.ops import pallas_kernels as pk

    tile = pk.spd_solve_tile(100)
    assert tile >= 128
    assert pk.spd_solve_vmem_bytes(100, tile) <= pk.SPD_SOLVE_VMEM_LIMIT
    one = SingleDeviceSharding(topo.devices[0])
    text = jax.jit(pk.spd_solve_lanes).lower(
        jax.ShapeDtypeStruct((5824, rows), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((104, rows), jnp.float32, sharding=one)
    ).compile().as_text()
    assert "tpu_custom_call" in text and pk.SPD_SOLVE_NAME in text


def test_the_em_iteration_reads_the_points_in_one_kernel(compiled):
    """The E-step of the cell's iteration is ONE kernel, ``em_estep`` under
    ``em.estep``, handed the stored points and the stacked operand's six
    bfloat16 passes side by side; the factorization's two custom calls are
    the TPU library's, and nothing row-sized but the points is made."""
    text = compiled("em-1-fused")
    mapped = scopes.scope_map(text)
    calls = [name for name, opcode in _loop_kernels(text)
             if opcode == "custom-call" and mapped[name] == "em.estep"]
    assert len(calls) == 1 and calls[0].startswith("em_estep"), calls
    assert {"Cholesky", "InvertDiagBlocksLowerTriangular"} <= set(
        re.findall(r'custom_call_target="(\w+)"', text))
    call = next(line for line in _loop_lines(text)
                if scopes._instruction(line.strip())[0] == calls[0])
    assert f"f32[{EM_POINTS},128]" in call and "bf16[10816,768]" in call
    assert not re.search(r"= \w+\[%d[,\]]" % EM_POINTS, "\n".join(
        line for line in text.splitlines() if " parameter(" not in line
        and "get-tuple-element(" not in line)), "an N-sized value is made"


def test_the_lda_sweep_samples_in_one_kernel_and_holds_no_tokens_by_topics(
        topo, no_compile_cache):
    """A group is ONE kernel, ``lda_sample`` under ``lda.sample``, handed the
    stream and both tables; the step's scratch holds nothing of tokens x
    topics (99.5 M x 1,000 int32 would be 398 GB) and fits beside them."""
    step = _lda_step(topo)
    text = step.as_text()
    mapped = scopes.scope_map(text)
    calls = [name for name, opcode in _loop_kernels(text)
             if opcode == "custom-call" and mapped[name] == "lda.sample"]
    assert len(calls) == 1 and calls[0].startswith("lda_sample"), calls
    assert step.memory_analysis().temp_size_in_bytes < 2 ** 28
    assert not re.search(r"\[%d,1000\]|\[%d,1024\]" % (LDA_TOKENS,
                                                         LDA_TOKENS), text)


def test_the_lda_kernel_fits_vmem_at_the_largest_group_it_admits(
        topo, no_compile_cache):
    """Mosaic accepts ``lda_sample`` under its VMEM limit at the most
    documents a group that the dispatch hands it: the two copies of the
    group's document rows beside the fixed part (staging, p, the word block
    and its run's change)."""
    from jax.sharding import SingleDeviceSharding

    from harp_tpu.ops import lda_kernels as lk

    dg = max(d for d in range(8, 2 ** 15, 8)
             if lk.use_lda_sample_pallas("tpu", 1000, 1, d))
    assert dg == 12_800
    one = SingleDeviceSharding(topo.devices[0])
    max_tiles, slots = 3_600, 2 * 3_600 * lk.TILE
    tile = (lk.SUBLANES, lk.LANES)

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    sample = functools.partial(lk.sample_pallas, k=1000, vocab=102_660,
                               alpha=0.05, beta=0.01, dg=dg)
    text = jax.jit(sample).lower(
        shaped((4,)), shaped((max_tiles,)), shaped((max_tiles,)),
        shaped((slots,)), shaped((slots,)), shaped((slots,)),
        shaped((max_tiles * lk.TILE,), jnp.float32),
        shaped((102_784, *tile)), shaped((299_752 + dg, *tile)),
        shaped(tile)).compile().as_text()
    assert "tpu_custom_call" in text and lk.NAME in text


def test_the_fused_hop_picks_its_block_out_of_the_whole_slab(compiled):
    """On four workers the resident block changes every hop. The fused
    program holds no block-sized bf16 result in a loop body (neither the
    pick's copy nor a layout copy of the slab) and its kernel reads an
    operand of the slab's whole shape; the XLA stripe scan still copies its
    block, under ``sgdmf.select``."""
    workers, rows, cpb = 4, 8 * 128, 6912       # _sgdmf_text's shapes
    block = rows * cpb
    fused = compiled("sgdmf-4-fused")
    assert not _block_sized_bf16(_loop_lines(fused), block)
    hops = [line for line in _loop_lines(fused) if "custom-call(" in line
            and "tpu_custom_call" in line]
    assert hops
    for line in hops:
        assert _bf16_shape((workers, rows, cpb)) in line.split(
            "custom-call(")[1], line

    xla = compiled("sgdmf-4")
    mapped = scopes.scope_map(xla)
    copies = [name for name, _, dims in _block_sized_bf16(
        _loop_lines(xla), block) if dims.endswith(f"{rows},{cpb}")]
    assert copies and {mapped[name] for name in copies} == {"sgdmf.select"}


def test_the_ring_hop_is_a_collective_permute_under_its_own_name(compiled):
    text = compiled("sgdmf-4")
    mapped = scopes.scope_map(text)
    hops = [name for name, op in _loop_kernels(text)
            if op == "collective-permute"]
    assert hops and {mapped[name] for name in hops} == {"rotation.hop"}


def test_on_the_chip_the_hop_kernel_sends_the_ring_hop_itself(compiled):
    """Where the chip's backend runs the fused kernel on a ring of four, the
    program leaves the wire to the kernel (it streams each finished tile of
    H to its neighbour): no collective-permute is left in the program, the
    kernel returns the received block beside its own three results, and the
    RMSE's psum is the loops' one collective."""
    text = compiled("sgdmf-4-ring-fused")
    assert "collective-permute" not in text
    (call,) = [line for line in _loop_lines(text) if "custom-call(" in line
               and "tpu_custom_call" in line]
    assert call.split("=")[0].strip().startswith("%dense_mf_hop")
    outs = call.split("=", 1)[1].split("custom-call(")[0]
    assert outs.count("f32[104,6912]") == 2, outs      # H updated, H received
    assert {op for _, op in _loop_kernels(text)} & {
        "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute"} == set()


def test_scope_map_gives_none_without_a_scope(compiled):
    mapped = scopes.scope_map(compiled("sgdmf-4"))
    # the program's argument, relaid at entry, is under no scope of its own
    outside = [name for name, scope in mapped.items() if scope is None]
    assert outside
    assert scopes.scope_of("jit(fit_fn)/shard_map/while/body/add") is None
    assert scopes.scope_of("jit(f)/kmeans.update/while/body/kmeans.stats/"
                           "dot_general") == "kmeans.stats"


def test_scope_map_reads_op_names_and_hands_names_to_what_the_compiler_made():
    text = """HloModule m
%body (p: (f32[8], f32[8])) -> (f32[8], f32[8]) {
  %p = (f32[8], f32[8]) parameter(0)
  %gte = f32[8] get-tuple-element(%p), index=0
  %fusion.1 = f32[8] fusion(%gte), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/kmeans.update/while/body/kmeans.stats/mul"}
  %copy.1 = f32[8] copy(%fusion.1)
  %copy.2 = f32[8] copy(%gte)
  %add.3 = f32[8] add(%copy.2, %copy.2), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple = (f32[8], f32[8]) tuple(%copy.1, %add.3)
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %copy.9 = f32[8] copy(%a), metadata={op_name="args[0]"}
  %while.1 = (f32[8], f32[8]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/kmeans.update/while"}
  ROOT %out = f32[8] get-tuple-element(%while.1), index=0
}
"""
    mapped = scopes.scope_map(text)
    assert mapped["fusion.1"] == "kmeans.stats"         # deepest listed
    assert mapped["while.1"] == "kmeans.update"
    assert mapped["add.3"] is None                      # named, not listed
    assert mapped["copy.9"] is None
    assert mapped["copy.1"] == "kmeans.stats"           # reads fusion.1
    # no neighbour with a scope: the loop it stands in names it
    assert mapped["copy.2"] == "kmeans.update"


def test_scoped_refuses_a_name_that_is_not_listed():
    with pytest.raises(ValueError, match="SCOPES"):
        scopes.scoped("kmeans.typo")
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)


def test_the_list_stands_below_the_wrapper_every_scoped_kernel_carries():
    """A name added to ``SCOPES`` must move no line of ``scoped``: a Pallas
    kernel traced under a scoped function carries the wrapper's source line,
    and the compile cache's key with it (PERF.md, Findings, PR 31, 3)."""
    import inspect

    lines, first = inspect.getsourcelines(scopes.scoped)
    source = inspect.getsource(scopes).splitlines()
    listed = [i + 1 for i, line in enumerate(source)
              if line.startswith(("SCOPES = (", "_TABLE_OPS = (",
                                  "_LAX_OPS = ("))]
    assert len(listed) == 3 and min(listed) > first + len(lines)


def test_device_time_by_scope_sums_to_the_ops_self_time():
    from benchmark import trace_reduce

    devices, _ = trace_reduce.read_planes(TRACE)
    ops = devices[0].ops
    own = trace_reduce._self_times(ops)          # by XLA's names
    containers = {n for n in own if n.split(".")[0] == "while"}
    assert containers
    # XLA's names of the recorded PR 24 trace, mapped by hand
    by_hand = {"fusion.19": "kmeans.scores",
               "multiply_reduce_fusion.4": "kmeans.stats",
               "fusion.20": "kmeans.stats",
               "multiply_reduce_fusion.1": "kmeans.norms"}
    got = scopes.device_time_by_scope(TRACE, by_hand)
    kernels = sum(v for k, v in own.items() if k not in containers)
    assert sum(got.values()) == pytest.approx(kernels, rel=1e-9)
    assert got["kmeans.scores"] == pytest.approx(own["fusion.19"], rel=1e-9)
    assert got["kmeans.stats"] == pytest.approx(
        own["multiply_reduce_fusion.4"] + own["fusion.20"], rel=1e-9)
    # a while is not counted again for its body: the whole is below the
    # span from the first event to the last
    span = max(e.end for e in ops) - min(e.start for e in ops)
    assert sum(got.values()) < span
    assert got[None] < 0.01 * sum(got.values())      # four kernels: 99 %
    assert scopes.device_time_by_scope(TRACE, by_hand, device=1) == {}


def test_the_command_prints_a_table(capsys, tmp_path):
    hlo = tmp_path / "step.hlo.txt"
    hlo.write_text('  %fusion.19 = f32[8] fusion(%x), kind=kLoop, calls=%f, '
                   'metadata={op_name="jit(f)/kmeans.scores/dot_general"}\n')
    assert scopes.main([TRACE, str(hlo)]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert lines[0].split()[0] == "(no" or lines[0].startswith("kmeans.scores")
    assert any(line.startswith("kmeans.scores") for line in lines)
    assert scopes.main([str(tmp_path), str(hlo)]) == 1       # no trace there
    assert scopes.main([]) == 2


# --------------------------------------------------------------------------- #
# the other half of a trace: idle time by host phase (ISSUE 36)
# --------------------------------------------------------------------------- #

# nine calls of sgdmf-k100.ml10m on one v5e chip, driven as the harness
# drives them and traced as it traces (recorded by PR 36, seed 4294936022):
# the program's phases lie in its host plane beside the harness's spans
PHASED = os.path.join(os.path.dirname(TRACE),
                      "sgdmf_v5e_1chip_phases.xplane.pb")
HARNESS_SPANS = ("call", "fetch_quality", "job_reset")


def test_idle_by_phase_sums_to_the_idle_time():
    from benchmark import trace_reduce

    assert os.path.getsize(PHASED) < 200_000
    report = scopes.idle_by_phase(PHASED, span="window", also=HARNESS_SPANS)
    # the harness's own reduction of the same window: busy is the union of
    # the chip's XLA Ops, idle the window less it
    summary = trace_reduce.reduce(PHASED, spans=HARNESS_SPANS)
    assert report["span_s"] == pytest.approx(summary.window_s, rel=1e-9)
    assert report["idle_s"] == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    assert sum(report["by_phase"].values()) == pytest.approx(
        report["idle_s"], rel=1e-9)
    assert 0.0 < report["shift_s"] < 1e-3    # some hundreds of microseconds
    # with the program's names alone the same idle time, and what only the
    # harness's spans covered falls to host_other
    strict = scopes.idle_by_phase(PHASED, span="window")
    assert strict["idle_s"] == report["idle_s"]
    assert sum(strict["by_phase"].values()) == pytest.approx(
        strict["idle_s"], rel=1e-9)
    assert set(strict["by_phase"]) == {
        "step.fetch", "step.dispatch", "sgd_mf.call", scopes.OTHER}
    for name in ("step.fetch", "step.dispatch", "sgd_mf.call"):
        assert strict["by_phase"][name] == report["by_phase"][name]
    assert strict["by_phase"][scopes.OTHER] == pytest.approx(
        sum(report["by_phase"][n] for n in (*HARNESS_SPANS, scopes.OTHER)),
        rel=1e-9)
    assert scopes.idle_by_phase(PHASED, device=1) is None
    assert scopes.idle_by_phase(PHASED, span="no-such-span") is None
    # the trace recorded before the program had phases holds none to read
    assert scopes.idle_by_phase(TRACE) is None


def test_idle_by_phase_splits_a_gap_at_the_phase_boundaries():
    """The harness's reduction gives each gap whole to one span (``call``
    takes all of it here); between two step programs the host leaves the
    fetch that waited, ends the call, runs the harness's loop and is well
    into the next dispatch before the device starts again."""
    from benchmark import trace_reduce

    summary = trace_reduce.reduce(PHASED, spans=HARNESS_SPANS)
    assert [name for name, _ in summary.idle_gaps] == ["call"]
    report = scopes.idle_by_phase(PHASED, span="window", also=HARNESS_SPANS)
    by_phase, calls = report["by_phase"], report["calls"]
    assert len(calls) == len(summary.step_s) == 9
    # ten gaps (before, between and after nine programs), seven names
    assert set(by_phase) == {"step.fetch", "step.dispatch", "sgd_mf.call",
                             *HARNESS_SPANS, scopes.OTHER}
    assert all(v > 0.0 for v in by_phase.values())
    # the wait for the news of the end is most of it, the launch the next
    assert by_phase["step.fetch"] > 0.7 * report["idle_s"]
    assert by_phase["step.dispatch"] > by_phase["sgd_mf.call"] > 0.0
    # per call: the completion latencies are the idle time under step.fetch,
    # the launches the idle time under step.dispatch
    assert sum(c["completion_s"] for c in calls) == pytest.approx(
        by_phase["step.fetch"], rel=1e-4)
    assert sum(c["launch_s"] for c in calls) == pytest.approx(
        by_phase["step.dispatch"], rel=0.25)
    for c, step in zip(calls, summary.step_s):
        assert c["step_s"] == pytest.approx(step, rel=1e-9)
        assert 0.0 <= c["launch_s"] < 1e-3 < c["completion_s"] < 3e-3


def test_the_command_prints_the_idle_table(capsys):
    assert scopes.main([PHASED, "--idle", "--span", "window",
                        "--also", "call,fetch_quality"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert lines[0].startswith("device events shifted by 85.1 us")
    assert lines[1].startswith("step.fetch") and "%" in lines[1]
    assert any(line.startswith(scopes.OTHER) for line in lines)
    assert "9 step programs" in lines[7]
    assert len(lines) == 8 + 9 and len(lines[-1].split()) == 4
    assert scopes.main([PHASED, "--idle"]) == 0          # first dispatch on
    capsys.readouterr()
    assert scopes.main([TRACE, "--idle"]) == 1           # no phases in it
    assert scopes.main(["--idle"]) == 2
