"""PageRank / MDS / EM / quality / boosting / trees / apriori / subgraph tests
(contrib simplepagerank, wdamds, daal_em, daal_quality_metrics, daal_{stump,
adaboost,logitboost,brownboost}, daal_dtree/dforest, daal_ar, sahad parity)."""

import numpy as np
import pytest

from harp_tpu.io import datagen
from harp_tpu.models import (assoc, boosting, em, forest, mds, pagerank,
                             quality, subgraph)


def _ring_edges(n):
    src = np.arange(n, dtype=np.int64)
    dst = (src + 1) % n
    return src, dst


def test_pagerank_uniform_on_ring(session):
    n = 24
    src, dst = _ring_edges(n)
    pr = pagerank.PageRank(session, pagerank.PageRankConfig(iterations=30))
    ranks, deltas = pr.run(src, dst, n)
    np.testing.assert_allclose(ranks, 1.0 / n, atol=1e-4)
    assert deltas[-1] < 1e-5


def test_pagerank_matches_numpy_power_iteration(session):
    rng = np.random.default_rng(7)
    n, m = 40, 200
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    cfg = pagerank.PageRankConfig(damping=0.85, iterations=50)
    ranks, _ = pagerank.PageRank(session, cfg).run(src, dst, n)
    # numpy reference with same dangling handling
    deg = np.bincount(src, minlength=n).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for _ in range(50):
        contrib = np.zeros(n)
        np.add.at(contrib, dst, r[src] / deg[src])
        dangling = r[deg == 0].sum()
        r = (1 - 0.85) / n + 0.85 * (contrib + dangling / n)
    np.testing.assert_allclose(ranks, r, atol=1e-4)
    np.testing.assert_allclose(ranks.sum(), 1.0, atol=1e-3)


def test_mds_recovers_geometry(session):
    rng = np.random.default_rng(4)
    pts = rng.standard_normal((48, 2)).astype(np.float32)
    d = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1)).astype(np.float32)
    model = mds.WDAMDS(session, mds.MDSConfig(dim=2, iterations=80))
    x, stress = model.fit(d, seed=1)
    # a job is the whole annealing schedule, in calls of 80 iterations
    assert len(stress) == 320 >= mds.schedule_iterations(model.config)
    assert stress[-1] < 0.05 * stress[0]
    # embedded distances match target distances (up to rigid motion)
    d_emb = np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1))
    assert np.abs(d_emb - d).mean() < 0.1 * d.mean()


def test_wda_mds_weighted_cg_matches_numpy_oracle(session):
    """The distributed weighted V CG solve (WDAMDSMapper.java:585 parity)
    matches a single-host SMACOF-with-CG oracle on NON-uniform weights —
    the case where the old uniform V+=I/n simplification was a genuinely
    different algorithm — over the whole annealing schedule."""
    rng = np.random.default_rng(11)
    n = 48
    pts = rng.standard_normal((n, 2)).astype(np.float32)
    d = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1)).astype(np.float32)
    w = rng.uniform(0.2, 3.0, (n, n)).astype(np.float32)
    w = (w + w.T) / 2.0                     # symmetric, strongly non-uniform
    cfg = mds.MDSConfig(dim=2, iterations=25, cg_iters=20)
    x, stress = mds.WDAMDS(session, cfg).fit(d, weights=w, seed=1)
    # oracle with the identical init, schedule and truncated CG
    x0 = np.random.default_rng(1).standard_normal((n, 2)).astype(np.float32)
    x0 -= x0.mean(axis=0)
    x_ref, s_ref = mds.numpy_wda_smacof(d, w, x0, cfg, len(stress))
    np.testing.assert_allclose(stress, s_ref, rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(x, x_ref - x_ref.mean(axis=0),
                               rtol=1e-2, atol=1e-2)
    # the curve first rises (the hottest targets are nearly all 0)
    assert stress[1] > stress[0] and stress[-1] < 1e-3
    # and the weighted fit still embeds the geometry
    d_emb = np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1))
    assert np.abs(d_emb - d).mean() < 0.15 * d.mean()


@pytest.mark.parametrize("weights_dtype", ["float32", "bfloat16"])
def test_mds_matmuls_request_highest_precision(session, weights_dtype):
    """Regression guard for a REAL-CHIP-only failure the CPU suite cannot
    reproduce: TPU's default f32 matmul truncates operands to bf16, which
    sign-flips the CG's pᵀVp at convergence scale and sent the embedding to
    overflow (stress NaN at iteration 1 on hardware, round 5). A SMACOF
    product of float32 operands must pin Precision.HIGHEST; the one with
    bfloat16 weights states its precision by type (three exact bfloat16
    terms of the direction, ops/mds_kernels.py). B(X)X and the distances
    are elementwise float32: no product at all. Assert it in the lowered
    program."""
    from harp_tpu.models.mds import MDSConfig, _geometry, _train

    n = 16
    cfg = MDSConfig(dim=2, iterations=1)
    geom = _geometry(n // session.num_workers, n, cfg.dim, weights_dtype)
    prog = session.spmd(
        lambda d, w, v, s, xt, c: _train(d, w, v, s, xt, c, geom, cfg),
        in_specs=(session.shard(),) * 3 + (session.replicate(),) * 3,
        out_specs=(session.replicate(),) * 3)
    text = prog.lower(np.zeros((n, n), np.float32),
                      np.zeros((n, n), weights_dtype),
                      np.zeros((n,), np.float32), np.ones((2,), np.float32),
                      np.zeros((8, n), np.float32), np.int32(0)).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots, "no dot_general in the SMACOF program?"
    if weights_dtype == "float32":
        low = [ln for ln in dots if "HIGHEST" not in ln]
        assert not low, f"SMACOF matmuls without HIGHEST precision: {low}"
    else:
        wide = [ln for ln in dots if "xbf16>, tensor<" not in ln
                or "xbf16>) ->" not in ln]
        assert not wide, f"a product with an operand wider than bf16: {wide}"


def test_em_gmm_recovers_components(session):
    rng = np.random.default_rng(9)
    centers = np.array([[0, 0], [6, 0], [0, 6]], np.float32)
    x = np.concatenate([
        c + rng.standard_normal((80, 2)).astype(np.float32) for c in centers])
    rng.shuffle(x)
    model = em.EMGMM(session, em.EMConfig(num_components=3, iterations=40))
    pi, mean, cov, ll = model.fit(x, seed=3)
    assert ll[-1] > ll[0]
    np.testing.assert_allclose(sorted(pi), [1 / 3] * 3, atol=0.08)
    # every true center has a recovered mean nearby
    for c in centers:
        assert np.min(np.linalg.norm(mean - c, axis=1)) < 0.6


def test_quality_metrics(session):
    rng = np.random.default_rng(2)
    y = rng.integers(0, 3, 240).astype(np.int32)
    pred = y.copy()
    flip = rng.random(240) < 0.2
    pred[flip] = (pred[flip] + 1) % 3
    qm = quality.QualityMetrics(session)
    out = qm.classification(y, pred, 3)
    assert abs(out["accuracy"] - (y == pred).mean()) < 1e-5
    assert out["confusion"].sum() == 240
    # AUC: separable scores → ~1; random scores → ~0.5
    yb = rng.integers(0, 2, 240).astype(np.int32)
    assert qm.auc(yb, yb + 0.1 * rng.random(240).astype(np.float32)) > 0.99
    reg = qm.regression(np.arange(240, dtype=np.float32),
                        np.arange(240, dtype=np.float32) + 1.0)
    assert abs(reg["rmse"] - 1.0) < 1e-4 and reg["r2"] > 0.99


@pytest.fixture(scope="module")
def clf_data():
    rng = np.random.default_rng(11)
    n = 320
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = ((x[:, 0] + 0.5 * x[:, 1] - 0.3 * x[:, 2]) > 0).astype(np.int32)
    return x, y


def test_stump_and_adaboost(session, clf_data):
    x, y = clf_data
    stump = boosting.DecisionStump(session).fit(x, y)
    acc_stump = (stump.predict(x) == y).mean()
    assert acc_stump > 0.65
    ada = boosting.AdaBoost(session, boosting.BoostConfig(rounds=30)).fit(x, y)
    acc_ada = (ada.predict(x) == y).mean()
    assert acc_ada > acc_stump
    assert acc_ada > 0.85


def test_logitboost_and_brownboost(session, clf_data):
    x, y = clf_data
    lb = boosting.LogitBoost(session, boosting.BoostConfig(rounds=30)).fit(x, y)
    assert (lb.predict(x) == y).mean() > 0.85
    bb = boosting.BrownBoost(session, boosting.BoostConfig(rounds=30)).fit(x, y)
    assert (bb.predict(x) == y).mean() > 0.8


def test_decision_tree_and_forest(session):
    rng = np.random.default_rng(21)
    n = 400
    x = rng.standard_normal((n, 5)).astype(np.float32)
    # axis-aligned XOR-ish target: tree-friendly, linear-unfriendly
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.int32)
    tree = forest.DecisionTree(session, forest.TreeConfig(depth=3, num_bins=16,
                                                          num_classes=2))
    tree.fit(x, y)
    assert (tree.predict(x) == y).mean() > 0.9
    rf = forest.RandomForest(session, forest.TreeConfig(
        depth=3, num_bins=16, num_classes=2, num_trees=8,
        feature_fraction=0.8))
    rf.fit(x, y, seed=1)
    assert (rf.predict(x) == y).mean() > 0.9


def test_apriori(session):
    rng = np.random.default_rng(5)
    n, d = 240, 8
    tx = (rng.random((n, d)) < 0.15).astype(np.float32)
    # plant a strong pattern: items 0,1 co-occur in 40% of transactions
    planted = rng.random(n) < 0.4
    tx[planted, 0] = 1.0
    tx[planted, 1] = 1.0
    model = assoc.Apriori(session, assoc.AprioriConfig(
        min_support=0.2, min_confidence=0.6, max_size=3))
    model.fit(tx)
    assert (0,) in model.itemsets and (0, 1) in model.itemsets
    assert abs(model.itemsets[(0, 1)] - tx[:, [0, 1]].all(1).mean()) < 1e-6
    assert any(set(a) | set(c) == {0, 1} for a, c, _, _ in model.rules)


def test_subgraph_edge_count_exact_expectation(session):
    # k=2 template: "paths" of 2 vertices = edges; per-trial estimates are
    # exactly the edge count (every 2-coloring counts each edge with p=1/2,
    # unbiased correction 1/p = 2) up to coloring noise — mean over trials
    rng = np.random.default_rng(6)
    n, m = 32, 80
    src = rng.integers(0, n, m)
    dst = (src + 1 + rng.integers(0, n - 1, m)) % n
    cfg = subgraph.SubgraphConfig(template_size=2, trials=64)
    est, trials = subgraph.SubgraphCounter(session, cfg).count_paths(
        src, dst, n, seed=2)
    assert abs(est - m) < 0.25 * m


def test_subgraph_k4_three_paths(session):
    # K4: number of simple 3-vertex paths = 3 * C(4,3) = 12
    edges = [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)]
    src = np.array([e[0] for e in edges])
    dst = np.array([e[1] for e in edges])
    cfg = subgraph.SubgraphConfig(template_size=3, trials=96)
    est, _ = subgraph.SubgraphCounter(session, cfg).count_paths(src, dst, 4,
                                                                seed=7)
    assert abs(est - 12.0) < 6.0


def test_tree_template_automorphisms():
    t = subgraph.TreeTemplate
    assert t([(0, 1)]).automorphisms() == 2                       # edge
    assert t([(0, 1), (1, 2)]).automorphisms() == 2               # path-3
    assert t([(0, 1), (1, 2), (2, 3), (3, 4)]).automorphisms() == 2  # u5-1
    assert t([(0, 1), (0, 2), (0, 3), (0, 4)]).automorphisms() == 24  # star-5
    # spider S(2,1,1): center 1, legs 2-3 / 0 / 4 — the two single leaves swap
    assert t([(0, 1), (1, 2), (2, 3), (1, 4)]).automorphisms() == 2
    # the 7-vertex identity tree (legs of lengths 1,2,3) has aut = 1
    assert t([(0, 1), (0, 2), (2, 3), (0, 4), (4, 5),
              (5, 6)]).automorphisms() == 1
    with pytest.raises(ValueError):
        t([(0, 1), (0, 1)])                                       # dup edge
    with pytest.raises(ValueError):
        t([(0, 1), (2, 3)])                                       # forest


def test_tree_templates_match_brute_force(session):
    """VERDICT #3: general tree templates (u5-1 path, u5-2 spider, star,
    caterpillar) agree with exact backtracking counts on random graphs."""
    rng = np.random.default_rng(11)
    n, m = 24, 60
    src = rng.integers(0, n, m)
    dst = (src + 1 + rng.integers(0, n - 1, m)) % n
    templates = {
        "u3-star": [(0, 1), (0, 2), (0, 3)],
        "u5-1-path": [(0, 1), (1, 2), (2, 3), (3, 4)],
        "u5-star": [(0, 1), (0, 2), (0, 3), (0, 4)],
        "u5-2-spider": [(0, 1), (1, 2), (2, 3), (1, 4)],
    }
    counter = subgraph.SubgraphCounter(
        session, subgraph.SubgraphConfig(trials=160))
    for name, edges in templates.items():
        exact = subgraph.brute_force_tree_count(edges, src, dst, n)
        est, trials = counter.count_template(edges, src, dst, n, seed=5)
        assert exact > 0, name
        assert abs(est - exact) < 0.3 * exact + 2.0, (
            f"{name}: est {est} vs exact {exact}")


def test_general_tree_dp_reproduces_path_counts(session):
    """The path case through the general DP matches exact path counts (the
    pre-rewrite behavior was verified against the same oracle)."""
    rng = np.random.default_rng(3)
    n, m = 20, 40
    src = rng.integers(0, n, m)
    dst = (src + 1 + rng.integers(0, n - 1, m)) % n
    path4 = [(0, 1), (1, 2), (2, 3)]
    exact = subgraph.brute_force_tree_count(path4, src, dst, n)
    cfg = subgraph.SubgraphConfig(template_size=4, trials=160)
    est, _ = subgraph.SubgraphCounter(session, cfg).count_paths(
        src, dst, n, seed=9)
    assert abs(est - exact) < 0.3 * exact + 2.0


def test_template_file_format_roundtrip(tmp_path, session):
    """The reference's .template format (u5-2: vertex count, edge count,
    edges) parses and counts — datasets/daal_subgraph/templates parity."""
    from harp_tpu.models import subgraph

    p = tmp_path / "u5-2.template"
    p.write_text("5\n4\n0 1\n0 2\n0 3\n3 4\n")
    edges = subgraph.load_template_file(str(p))
    assert edges == [(0, 1), (0, 2), (0, 3), (3, 4)]
    t = subgraph.TreeTemplate(edges)
    assert t.k == 5
    bad = tmp_path / "bad.template"
    bad.write_text("3\n2\n0 1\n")          # declares 2 edges, carries 1
    import pytest

    with pytest.raises(ValueError, match="declares"):
        subgraph.load_template_file(str(bad))
    oob = tmp_path / "oob.template"
    oob.write_text("3\n2\n0 1\n1 5\n")     # vertex 5 outside [0, 3)
    with pytest.raises(ValueError, match="outside"):
        subgraph.load_template_file(str(oob))
