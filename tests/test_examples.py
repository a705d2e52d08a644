"""Smoke tests for the examples/ launchers (reference: ml/java examples/ +
per-algorithm *Launcher classes run by contrib/test_scripts)."""

import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           JAX_PLATFORMS="cpu")


def test_collectives_tour_runs():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "collectives_tour.py")],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "allreduce" in out.stdout and "rotate" in out.stdout


def test_analytics_tour_runs():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "analytics_tour.py")],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ANALYTICS TOUR OK" in out.stdout


def test_kmeans_launcher_cli(tmp_path):
    work = str(tmp_path / "km")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "kmeans_launcher.py"),
         "--cpu-mesh", "1000", "10", "20", "8", "5", work],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    cen = np.loadtxt(os.path.join(work, "centroids.csv"), delimiter=",")
    assert cen.shape == (10, 20)
    assert "cost:" in out.stdout


def _run_cmd(args, timeout=300):
    return subprocess.run(
        [sys.executable, "-m", "harp_tpu.run"] + args + ["--cpu-mesh",
                                                         "--num-workers", "8"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_run_kmeans_cli(tmp_path):
    out = _run_cmd(["kmeans", "--num-points", "1024", "--num-centroids", "10",
                    "--dim", "16", "--iterations", "4",
                    "--work-dir", str(tmp_path)])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "iters/s" in out.stdout and "cost" in out.stdout
    assert np.loadtxt(os.path.join(str(tmp_path), "centroids.csv"),
                      delimiter=",").shape == (10, 16)


def test_run_sgd_mf_cli_with_checkpointing(tmp_path):
    args = ["sgd_mf", "--num-users", "128", "--num-items", "96", "--density",
            "0.2", "--rank", "8", "--epochs", "6", "--save-every", "2",
            "--work-dir", str(tmp_path)]
    out = _run_cmd(args)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "M samples/s" in out.stdout
    # checkpoints written; a re-run resumes (no epochs left to run)
    ckpts = os.listdir(os.path.join(str(tmp_path), "ckpt"))
    assert any(c.startswith("step_") for c in ckpts)
    out2 = _run_cmd(args)
    assert out2.returncode == 0, out2.stderr[-2000:]


def test_run_lda_cli():
    out = _run_cmd(["lda", "--num-docs", "64", "--vocab", "48",
                    "--num-topics", "4", "--doc-len", "16", "--epochs", "3"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "M tokens/s" in out.stdout and "ll" in out.stdout


def test_run_lda_cli_on_a_uci_docword_file(tmp_path):
    """A ragged UCI bag-of-words file: it runs on the stream layout with no
    option set; asked for CVB0, which the stream does not run, the run stops
    and says why."""
    path = tmp_path / "docword.tiny.txt"
    path.write_text("3\n12\n5\n1 1 3\n1 12 1\n2 4 2\n3 5 6\n3 2 1\n")
    out = _run_cmd(["lda", "--corpus-file", str(path),
                    "--num-topics", "4", "--epochs", "2",
                    "--groups-per-sweep", "2"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "lda[stream]" in out.stdout and "docs=3" in out.stdout
    refused = _run_cmd(["lda", "--method", "cvb0", "--corpus-file",
                        str(path), "--epochs", "1"])
    assert refused.returncode != 0 and "stream layout" in refused.stderr


def test_run_pca_cli():
    out = _run_cmd(["pca", "--num-points", "1024", "--dim", "16",
                    "--iterations", "2"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "fits/s" in out.stdout and "eigenvalue" in out.stdout


def test_run_nn_cli():
    out = _run_cmd(["nn", "--num-points", "512", "--dim", "8",
                    "--epochs", "3", "--num-classes", "2"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "train acc" in out.stdout


def test_run_sgd_mf_cli_adaptive():
    out = _run_cmd(["sgd_mf", "--num-users", "128", "--num-items", "96",
                    "--density", "0.2", "--rank", "8", "--epochs", "6",
                    "--adaptive"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tuned budget:" in out.stdout and "M samples/s" in out.stdout


# --- round-3 launcher surface: one smoke per remaining family (VERDICT #3) -- #

import pytest


@pytest.mark.parametrize("args,expect", [
    (["als", "--num-users", "256", "--num-items", "192", "--density", "0.05",
      "--rank", "8", "--iterations", "3"], "iters/s"),
    (["ccd", "--num-users", "128", "--num-items", "96", "--density", "0.1",
      "--rank", "4", "--outer-iterations", "3"], "sweeps/s"),
    (["mds", "--num-points", "64", "--dim", "2", "--iterations", "5"],
     "stress"),
    (["pagerank", "--num-vertices", "512", "--num-edges", "2048",
      "--iterations", "5"], "delta"),
    (["subgraph", "--num-vertices", "64", "--num-edges", "256",
      "--template-size", "3", "--trials", "2"], "estimate"),
    (["subgraph", "--num-vertices", "48", "--num-edges", "128",
      "--template", "0-1,1-2,1-3", "--trials", "2"], "estimate"),
    (["svm", "--num-points", "512", "--dim", "8", "--iterations", "20"],
     "train acc"),
    (["forest", "--num-points", "512", "--dim", "8", "--depth", "3",
      "--num-trees", "2"], "train acc"),
    (["boosting", "--kind", "ada", "--num-points", "512", "--dim", "8",
      "--rounds", "4"], "train acc"),
    (["solver", "--kind", "lbfgs", "--num-points", "512", "--dim", "8",
      "--iterations", "10"], "mse"),
    (["stats", "--op", "qr", "--num-points", "512", "--dim", "16"],
     "||QR-X||"),
    (["stats", "--op", "quantiles", "--num-points", "512", "--dim", "8"],
     "quartiles"),
    (["linear", "--num-points", "512", "--dim", "8", "--l2", "0.1"],
     "mse"),
    (["classifiers", "--kind", "mlr", "--num-points", "512", "--dim", "8",
      "--num-classes", "3"], "train acc"),
    (["classifiers", "--kind", "knn", "--num-points", "512", "--dim", "8",
      "--num-classes", "2"], "train acc"),
    (["classifiers", "--kind", "em", "--num-points", "512", "--dim", "4",
      "--num-classes", "2"], "ll"),
    (["apriori", "--num-transactions", "512", "--num-items", "16"],
     "frequent itemsets"),
    (["sgxsimu", "--num-points", "2048", "--num-centroids", "8", "--dim",
      "16", "--iterations", "4", "--page-swap", "--enclave-per-thd-mb", "1",
      "--simulate"], "modeled slowdown"),
])
def test_run_family_cli(args, expect):
    out = _run_cmd(args)
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    assert expect in out.stdout, out.stdout


def test_gang_tour_example():
    """The round-3 distributed-runtime tour: gang launch -> distributed CLI
    training with checkpoints -> full resume -> fail-stop."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "gang_tour.py")],
        env=ENV, capture_output=True, text=True, timeout=700)
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    assert "gang tour OK" in out.stdout
