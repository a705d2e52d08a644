"""Property tests for every collective against numpy references on an 8-worker mesh.

Reference test-strategy parity (SURVEY §4): Harp tested collectives via standalone
multi-JVM mains; here each op is asserted against the mathematically expected result.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harp_tpu
from harp_tpu import combiner as cb
from harp_tpu import partitioner as pt
from harp_tpu.collectives import lax_ops, table_ops
from harp_tpu.table import Dist, Table

W = 8
P_TOTAL = 16  # partitions
SHAPE = (P_TOTAL, 3, 5)


def spmd(session, fn, n_shard_args=0, n_rep_args=1, out="rep"):
    in_specs = tuple([session.shard()] * n_shard_args + [session.replicate()] * n_rep_args)
    out_specs = session.shard() if out == "shard" else session.replicate()
    return session.spmd(fn, in_specs=in_specs, out_specs=out_specs)


def per_worker_contributions(rng):
    # contributions[w] = worker w's LOCAL table data
    return rng.normal(size=(W,) + SHAPE).astype(np.float32)


def run_local_op(session, contribs, fn, out="rep"):
    """Feed worker w its own contribution: shard a (W, P, ...) array on axis 0."""
    def wrapper(c):
        t = Table.local(c[0], num_workers=W)  # c: (1, P, ...) local block
        return fn(t)
    return session.spmd(
        wrapper, in_specs=(session.shard(),), out_specs=(session.shard() if out == "shard" else session.replicate()),
    )(contribs)


class TestAllreduce:
    @pytest.mark.parametrize("op,ref", [
        (cb.SUM, lambda c: c.sum(0)),
        (cb.MAX, lambda c: c.max(0)),
        (cb.MIN, lambda c: c.min(0)),
        (cb.AVG, lambda c: c.mean(0)),
        (cb.MULTIPLY, lambda c: c.prod(0)),
        (cb.MINUS, lambda c: c[0] - c[1:].sum(0)),
    ])
    def test_allreduce(self, session, rng, op, ref):
        contribs = per_worker_contributions(rng)

        def f(c):
            t = Table.local(c[0], combiner=op, num_workers=W)
            return table_ops.allreduce(t).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.replicate())(contribs)
        np.testing.assert_allclose(np.asarray(out), ref(contribs), rtol=2e-5)


class TestReduceBroadcastGather:
    def test_reduce_root_gets_sum_others_identity(self, session, rng):
        contribs = per_worker_contributions(rng)

        def f(c):
            t = Table.local(c[0], num_workers=W)
            return table_ops.reduce(t, root=2).data

        # out_specs sharded: recover each worker's private view
        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(contribs)
        out = np.asarray(out).reshape((W,) + SHAPE)
        np.testing.assert_allclose(out[2], contribs.sum(0), rtol=2e-5)
        for w in range(W):
            if w != 2:
                np.testing.assert_array_equal(out[w], np.zeros(SHAPE, np.float32))

    def test_broadcast(self, session, rng):
        contribs = per_worker_contributions(rng)

        def f(c):
            t = Table.local(c[0], num_workers=W)
            return table_ops.broadcast(t, root=3).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.replicate())(contribs)
        np.testing.assert_allclose(np.asarray(out), contribs[3], rtol=1e-6)

    def test_gather(self, session, rng):
        blocks = rng.normal(size=SHAPE).astype(np.float32)  # block w = partitions of w

        def f(b):
            t = Table.sharded(b, num_workers=W)
            return table_ops.gather(t, root=0).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(blocks)
        out = np.asarray(out).reshape((W,) + SHAPE)
        np.testing.assert_allclose(out[0], blocks, rtol=1e-6)
        assert np.all(out[1:] == 0)


class TestRegroupAllgather:
    @pytest.mark.parametrize("op,ref", [
        (cb.SUM, lambda c: c.sum(0)),
        (cb.MAX, lambda c: c.max(0)),
    ])
    def test_regroup_block(self, session, rng, op, ref):
        contribs = per_worker_contributions(rng)

        def f(c):
            t = Table.local(c[0], combiner=op, num_workers=W)
            return table_ops.regroup(t).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(contribs)
        # sharded out: concatenated blocks in worker order = combined table in ID order
        np.testing.assert_allclose(np.asarray(out), ref(contribs), rtol=2e-5)

    def test_aggregate_equals_allreduce(self, session, rng):
        contribs = per_worker_contributions(rng)

        def f(c):
            t = Table.local(c[0], num_workers=W)
            return table_ops.aggregate(t).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.replicate())(contribs)
        np.testing.assert_allclose(np.asarray(out), contribs.sum(0), rtol=2e-5)

    def test_regroup_allgather_modulo_partitioner(self, session, rng):
        contribs = per_worker_contributions(rng)
        part = pt.ModuloPartitioner(P_TOTAL, W)

        def f(c):
            t = Table.local(c[0], num_workers=W)
            g = table_ops.regroup(t, part)
            return table_ops.allgather(g, part).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.replicate())(contribs)
        # ID order must be restored exactly
        np.testing.assert_allclose(np.asarray(out), contribs.sum(0), rtol=2e-5)

    def test_modulo_partitioner_places_partitions_on_owners(self, session, rng):
        contribs = per_worker_contributions(rng)
        part = pt.ModuloPartitioner(P_TOTAL, W)

        def f(c):
            t = Table.local(c[0], num_workers=W)
            return table_ops.regroup(t, part).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(contribs)
        out = np.asarray(out).reshape((W, P_TOTAL // W) + SHAPE[1:])
        total = contribs.sum(0)
        for w in range(W):
            # worker w owns partitions with pid % W == w, in ascending pid order
            pids = [pid for pid in range(P_TOTAL) if pid % W == w]
            np.testing.assert_allclose(out[w], total[pids], rtol=2e-5)


class TestRotate:
    def test_rotate_ring(self, session, rng):
        blocks = rng.normal(size=SHAPE).astype(np.float32)

        def f(b):
            t = Table.sharded(b, num_workers=W)
            return table_ops.rotate(t, steps=1).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(blocks)
        out = np.asarray(out).reshape((W, P_TOTAL // W) + SHAPE[1:])
        src = blocks.reshape((W, P_TOTAL // W) + SHAPE[1:])
        for w in range(W):
            np.testing.assert_allclose(out[(w + 1) % W], src[w], rtol=1e-6)

    def test_full_rotation_cycle_restores(self, session, rng):
        blocks = rng.normal(size=SHAPE).astype(np.float32)

        def f(b):
            t = Table.sharded(b, num_workers=W)
            def body(i, tt):
                return table_ops.rotate(tt, steps=1)
            return jax.lax.fori_loop(0, W, body, t).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(blocks)
        np.testing.assert_allclose(np.asarray(out), blocks, rtol=1e-6)

    def test_rotate_with_map(self, session, rng):
        blocks = rng.normal(size=SHAPE).astype(np.float32)
        mapping = {i: (i + 3) % W for i in range(W)}

        def f(b):
            t = Table.sharded(b, num_workers=W)
            return table_ops.rotate_with_map(t, mapping).data

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(blocks)
        out = np.asarray(out).reshape((W, P_TOTAL // W) + SHAPE[1:])
        src = blocks.reshape((W, P_TOTAL // W) + SHAPE[1:])
        for w in range(W):
            np.testing.assert_allclose(out[(w + 3) % W], src[w], rtol=1e-6)


class TestPushPull:
    def test_push_pull_parameter_server(self, session, rng):
        global_init = rng.normal(size=SHAPE).astype(np.float32)
        contribs = per_worker_contributions(rng)

        def f(g_block, c):
            g = Table.sharded(g_block, num_workers=W)
            local = Table.local(c[0], num_workers=W)
            g2 = table_ops.push(local, g)
            return table_ops.pull(g2).data

        out = session.spmd(
            f, in_specs=(session.shard(), session.shard()),
            out_specs=session.replicate())(global_init, contribs)
        np.testing.assert_allclose(np.asarray(out), global_init + contribs.sum(0),
                                   rtol=2e-5)


class TestGroupByKey:
    def test_group_by_key_sum(self, session, rng):
        keys = rng.integers(0, 10, size=(W, 6)).astype(np.int32)
        vals = rng.normal(size=(W, 6, 4)).astype(np.float32)

        def f(k, v):
            return table_ops.group_by_key(k[0], v[0], num_keys=10)

        out = session.spmd(f, in_specs=(session.shard(), session.shard()),
                           out_specs=session.replicate())(keys, vals)
        ref = np.zeros((10, 4), np.float32)
        for w in range(W):
            for i in range(6):
                ref[keys[w, i]] += vals[w, i]
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=1e-5)


class TestLaxOps:
    def test_barrier_and_ids(self, session):
        def f():
            lax_ops.barrier()
            return lax_ops.worker_id()[None]

        out = session.spmd(f, in_specs=(), out_specs=session.shard())()
        np.testing.assert_array_equal(np.asarray(out), np.arange(W))

    def test_all_to_all_transpose(self, session, rng):
        x = rng.normal(size=(W, W, 2)).astype(np.float32)  # worker w sends row j to j

        def f(xl):
            return lax_ops.all_to_all(xl[0])

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(x)
        out = np.asarray(out).reshape(W, W, 2)
        np.testing.assert_allclose(out, x.transpose(1, 0, 2), rtol=1e-6)

    def test_send_recv(self, session, rng):
        x = rng.normal(size=(W, 3)).astype(np.float32)

        def f(xl):
            return lax_ops.send_recv(xl[0], [(0, 5)])

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.shard())(x)
        out = np.asarray(out).reshape(W, 3)
        np.testing.assert_allclose(out[5], x[0], rtol=1e-6)
        assert np.all(out[np.arange(W) != 5] == 0)


class TestTablePadding:
    def test_ragged_partition_count_pads_with_identity(self, session, rng):
        # 13 partitions on 8 workers -> padded to 16; MAX identity = -inf
        contribs = rng.normal(size=(W, 13, 4)).astype(np.float32)

        def f(c):
            t = Table.local(c[0], combiner=cb.MAX, num_workers=W)
            out = table_ops.allreduce(t)
            return out.trim()

        out = session.spmd(f, in_specs=(session.shard(),),
                           out_specs=session.replicate())(contribs)
        assert out.shape == (13, 4)
        np.testing.assert_allclose(np.asarray(out), contribs.max(0), rtol=2e-5)


class TestJoin:
    def test_join_colocates_with_static(self, session, rng):
        """GraphCollective.join parity: dynamic partitions land where the
        matching static partitions live, combining contributions."""
        import jax.numpy as jnp

        from harp_tpu import Table
        from harp_tpu.collectives import table_ops

        w = session.num_workers
        p = 2 * w

        def prog(static_block, contrib):
            static = Table.sharded(static_block, num_workers=w)
            dynamic = Table.local(contrib, num_workers=w, name="dyn")
            joined = table_ops.join(dynamic, static)
            # joined block i must sit beside static block i: same local shape
            return joined.data + 0.0 * static.data

        static_full = np.arange(p * 3, dtype=np.float32).reshape(p, 3)
        contrib = np.ones((p, 3), np.float32)
        out = session.run(
            prog, session.scatter(jnp.asarray(static_full)),
            session.replicate_put(jnp.asarray(contrib)),
            in_specs=(session.shard(), session.replicate()),
            out_specs=session.shard())
        # every worker contributed 1s for every partition -> combined value = W
        np.testing.assert_allclose(np.asarray(out), np.full((p, 3), w))

    def test_join_requires_matching_counts(self, session):
        import jax.numpy as jnp

        from harp_tpu import Table
        from harp_tpu.collectives import table_ops

        w = session.num_workers

        def prog(static_block):
            static = Table.sharded(static_block, num_workers=w)
            dynamic = Table.local(jnp.ones((4 * w, 2)), num_workers=w)
            return table_ops.join(dynamic, static).data

        import pytest

        with pytest.raises(ValueError, match="matching partition counts"):
            session.run(prog, session.scatter(jnp.ones((w, 2))),
                        in_specs=(session.shard(),), out_specs=session.shard())


class TestGroupByKeySharded:
    """Owner-partitioned shuffle (VERDICT #8): parity with the allgather
    implementation, O(N/W + K/W) intermediate shapes, overflow accounting."""

    def _run(self, session, keys, vals, num_keys, combiner=None, cap=0,
             replicate=True):
        from harp_tpu import combiner as cb

        combiner = combiner or cb.SUM

        def f(k, v):
            out, ovf = table_ops.group_by_key_sharded(
                k[0], v[0], num_keys=num_keys, combiner=combiner,
                capacity=cap, replicate_result=replicate)
            return (out if replicate else out[None]), ovf

        out_spec = session.replicate() if replicate else session.shard()
        return session.spmd(
            f, in_specs=(session.shard(), session.shard()),
            out_specs=(out_spec, session.replicate()))(keys, vals)

    def test_parity_with_allgather_group_by_key(self, session, rng):
        from harp_tpu import combiner as cb

        keys = rng.integers(0, 16, size=(W, 12)).astype(np.int32)
        vals = rng.normal(size=(W, 12, 3)).astype(np.float32)

        def ref_f(k, v):
            return table_ops.group_by_key(k[0], v[0], num_keys=16)

        ref = np.asarray(session.spmd(
            ref_f, in_specs=(session.shard(), session.shard()),
            out_specs=session.replicate())(keys, vals))
        flat_k = keys.reshape(-1)
        flat_v = vals.reshape(-1, 3)
        refs = {}
        refs[cb.SUM.op] = ref
        cnt = np.maximum(np.bincount(flat_k, minlength=16), 1)[:, None]
        refs[cb.AVG.op] = ref / cnt
        mx = np.full((16, 3), -np.inf, np.float32)
        mn = np.full((16, 3), np.inf, np.float32)
        np.maximum.at(mx, flat_k, flat_v)
        np.minimum.at(mn, flat_k, flat_v)
        refs[cb.MAX.op] = mx
        refs[cb.MIN.op] = mn
        present = np.bincount(flat_k, minlength=16) > 0
        for comb in (cb.SUM, cb.AVG, cb.MAX, cb.MIN):
            out, ovf = self._run(session, keys, vals, 16, comb, cap=12)
            assert int(ovf) == 0
            out = np.asarray(out)
            assert out.shape == (16, 3)
            np.testing.assert_allclose(out[present], refs[comb.op][present],
                                       rtol=2e-5, atol=1e-5)

    def test_sharded_result_block_and_footprint(self, session, rng):
        # replicate_result=False keeps only this worker's K/W key block, and
        # the bucket capacity (the only N-dependent intermediate) is the
        # requested O(N/W) size
        n_local, num_keys = 16, 32
        keys = rng.integers(0, num_keys, size=(W, n_local)).astype(np.int32)
        vals = rng.normal(size=(W, n_local)).astype(np.float32)
        cap = 2 * n_local // W + n_local % W + 4     # O(N/W), not O(N)
        out, ovf = self._run(session, keys, vals, num_keys, cap=cap,
                             replicate=False)
        assert int(ovf) == 0
        out = np.asarray(out)
        assert out.shape == (W, num_keys // W)       # per-worker key block
        ref = np.zeros(num_keys, np.float32)
        np.add.at(ref, keys.reshape(-1), vals.reshape(-1))
        np.testing.assert_allclose(out.reshape(-1), ref, rtol=2e-5, atol=1e-5)

    def test_overflow_is_counted_not_silent(self, session):
        # every record targets key 0 → destination bucket 0 overflows
        keys = np.zeros((W, 8), np.int32)
        vals = np.ones((W, 8), np.float32)
        out, ovf = self._run(session, keys, vals, 16, cap=2)
        assert int(ovf) == W * 8 - W * 2             # 2 survive per worker
        assert float(np.asarray(out)[0]) == W * 2.0

    def test_negative_keys_dropped_not_misrouted(self, session, rng):
        # advisor r2: a negative dest used to pass the d_s < w check and land
        # (clamped) in worker 0's bucket as a phantom delivery; now negatives
        # route to the virtual drop destination like valid=False rows
        keys = rng.integers(0, 16, size=(W, 10)).astype(np.int32)
        keys[:, ::3] = -rng.integers(1, 50, size=keys[:, ::3].shape)
        vals = np.ones((W, 10), np.float32)
        out, ovf = self._run(session, keys, vals, 16, cap=16)
        assert int(ovf) == 0                         # dropped, not overflow
        ref = np.zeros(16, np.float32)
        good = keys >= 0
        np.add.at(ref, keys[good], vals[good])
        np.testing.assert_allclose(np.asarray(out).reshape(-1), ref,
                                   rtol=1e-6)


class TestQuantizedBenchRows:
    """``harp_tpu.benchmark.collectives``' quantized rows: row schema +
    wire-byte pricing."""

    def test_quant_bytes_moved_prices_the_codec_wire_format(self):
        from harp_tpu.benchmark import collectives as bc

        s = 1 << 20
        f32_ar = bc._bytes_moved("allreduce", s, 8)
        bf16_ar = bc._quant_bytes_moved("allreduce", s, 8, "bf16")
        int8_ar = bc._quant_bytes_moved("allreduce", s, 8, "int8")
        assert bf16_ar == f32_ar / 2
        # int8 = 1/4 payload + per-256-elem f32 scales (~1.6% overhead)
        assert f32_ar / 4 < int8_ar < f32_ar / 4 * 1.05
        assert bc._quant_bytes_moved("rotate", s, 8, "bf16") == s / 2

    def test_bench_rows_emit_convention_and_all_codecs(self, session):
        from harp_tpu.benchmark import collectives as bc

        rows = bc.bench_collectives_quantized(session, sizes_kb=[4],
                                              loops=2)
        assert {r["codec"] for r in rows} == {"f32", "int8", "bf16"}
        assert {r["op"] for r in rows} == {"allreduce", "rotate"}
        for r in rows:
            assert r["payload_bytes_per_worker"] > 0
            assert r["busbw_gbps"] > 0
            assert r["link_class"] == "ici"
            assert "busbw" in r["convention"]
