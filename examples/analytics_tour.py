"""Analytics-suite tour — the ml/daal families end to end on one mesh.

Reference parity: the role of ml/daal's per-algorithm Launcher mains (each
daal_* family shipped a runnable example job). One script walks the r4
surface: dense + CSR analytics, PCA both methods, kernel/multiclass SVM,
WDA-MDS with non-uniform weights, distributed sort/quantiles, and the
fsspec IO seam. Run with:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/analytics_tour.py
"""

import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np                                     # noqa: E402

from harp_tpu.io import datagen, loaders               # noqa: E402
from harp_tpu.models import mds, sparse, stats, svm    # noqa: E402
from harp_tpu.session import HarpSession               # noqa: E402


def main():
    sess = HarpSession(num_workers=8)
    rng = np.random.default_rng(0)

    # --- dense analytics: covariance → PCA by both reference methods ----- #
    x = rng.standard_normal((512, 16)).astype(np.float32)
    cov, mean = stats.Covariance(sess).compute(x)
    assert np.allclose(cov, np.cov(x, rowvar=False), atol=1e-4)
    assert np.allclose(mean, x.mean(0), atol=1e-5)
    w_cor, _, _ = stats.PCA(sess, method="cor").fit(x)
    w_svd, _, _ = stats.PCA(sess, method="svd").fit(x)
    assert np.allclose(w_cor, w_svd, atol=1e-3)
    print(f"pca: top eigenvalue {w_cor[0]:.3f} (cor == svd method)")

    # --- CSR analytics: the same answers from sparse input --------------- #
    rows, cols, vals = datagen.sparse_points(512, 16, density=0.2, seed=1)
    cov_csr, _ = sparse.CSRCovariance(sess).compute(rows, cols, vals, 512, 16)
    dense = np.zeros((512, 16), np.float32)
    dense[rows, cols] = vals
    assert np.allclose(cov_csr, np.cov(dense, rowvar=False), atol=1e-4)
    cen, costs = sparse.SparseKMeans(
        sess, sparse.SparseKMeansConfig(4, 16, 5)).fit(
        rows, cols, vals, 512, dense[:4].copy())
    print(f"csr kmeans: cost {costs[0]:.1f} -> {costs[-1]:.1f}")

    # --- kernel SVM: rbf separates what linear cannot -------------------- #
    theta = rng.uniform(0, 2 * np.pi, 256)
    radius = np.where(np.arange(256) % 2 == 0, 1.0, 3.0)
    y = (np.arange(256) % 2 == 0).astype(np.int32)
    pts = (radius[:, None] * np.c_[np.cos(theta), np.sin(theta)]
           + 0.1 * rng.standard_normal((256, 2))).astype(np.float32)
    machine = svm.KernelSVM(sess, svm.KernelSVMConfig(
        kernel="rbf", c=10.0, iterations=250))
    machine.fit(pts, y)
    acc = (machine.predict(pts) == y).mean()
    print(f"kernel svm (rbf, circles): train acc {acc:.3f}, "
          f"{len(machine.sv_x)} support vectors")
    assert acc > 0.95

    # --- WDA-MDS: weighted CG Guttman solve ------------------------------ #
    p2 = rng.standard_normal((64, 2)).astype(np.float32)
    dist = np.sqrt(((p2[:, None] - p2[None]) ** 2).sum(-1)).astype(np.float32)
    wts = rng.uniform(0.5, 2.0, dist.shape).astype(np.float32)
    emb, stress = mds.WDAMDS(sess, mds.MDSConfig(
        dim=2, iterations=30, cg_iters=10)).fit(dist, weights=(wts + wts.T) / 2)
    print(f"wda-mds: stress {stress[0]:.1f} -> {stress[-1]:.1f}")
    assert stress[-1] < stress[0]

    # --- distributed order statistics ------------------------------------ #
    q = stats.Quantiles(sess).compute(x, [0.25, 0.5, 0.75])
    assert np.allclose(q, np.quantile(x, [0.25, 0.5, 0.75], axis=0),
                       atol=1e-4)
    print(f"quantiles (distributed sort): median[0] {q[1, 0]:.3f}")

    # --- fsspec seam: part-files in an object store ---------------------- #
    import fsspec

    with fsspec.open("memory://tour/part-0.csv", "w") as f:
        for row in x[:8]:
            f.write(",".join(f"{v:.6f}" for v in row) + "\n")
    loaded = loaders.load_dense_csv(loaders.list_files("memory://tour/"))
    assert loaded.shape == (8, 16)
    fsspec.filesystem("memory").rm("/tour", recursive=True)
    print("fsspec seam: memory:// part-file round trip OK")
    print("ANALYTICS TOUR OK")


if __name__ == "__main__":
    main()
