"""Per-step budget of mini-batch NN training (VERDICT r4 weak #1).

The r4 bench config (n=65536, d=128, layers 256x128, batch 512) recorded
nn_vs_xeon36_lb = 1.36 at 0.87% MFU with no evidence of WHERE the step time
goes. This harness measures, all two-point (the per-call dispatch constant
cancels — bench.py r5):

* a **batch-size sweep** at the bench model (512 → 4096 → full batch):
  per-step µs vs per-step FLOPs separates the fixed per-step cost (scan/
  optimizer/dispatch of many small GEMMs) from compute — if µs/step is flat
  while FLOPs/step grows 8x, the 512-batch config sits at a latency floor no
  formulation can move, which is the honest framing BASELINE's toy shape
  earns;
* the **compute-bound config** (d=512, layers 2048x1024, batch 8192) the r5
  bench adds as its second NN row;
* the **allreduce share** on the 8-worker virtual CPU mesh: full step vs
  ``ablate_allreduce=True`` (timing-only knob) — an UPPER bound for real ICI
  (host-shared-core collectives price higher relative to compute).

Run::

    python -m harp_tpu.benchmark.nn_budget            # real chip part
    python -m harp_tpu.benchmark.nn_budget --mesh     # virtual-mesh part

Prints one JSON line; PERF.md records the numbers.
"""

from __future__ import annotations

import json
import sys


def _two_point_epoch_s(sess, n, d, layers, batch, epochs, reps=3, **cfg_kw):
    """Two-point seconds per epoch for one NN config (shared alternating
    protocol, benchmark/timing.py — the per-call constant cancels)."""
    import jax.numpy as jnp

    from harp_tpu.benchmark.timing import two_point
    from harp_tpu.io import datagen
    from harp_tpu.models import nn

    x, y = datagen.classification_data(n, d, 16, seed=4)
    x_dev = sess.scatter(jnp.asarray(x, jnp.float32))
    y_dev = sess.scatter(jnp.asarray(y, jnp.int32))

    def build(ne):
        cfg = nn.NNConfig(layers=layers, num_classes=16, lr=0.05,
                          batch_size=batch, epochs=ne, **cfg_kw)
        m = nn.MLPClassifier(sess, cfg)
        m.fit(x_dev, y_dev, seed=0)              # compile + warm

        def timer():
            m.fit(x_dev, y_dev, seed=0)
        return timer

    tp = two_point(build, max(epochs // 4, 1), epochs, 1.0, reps=reps)
    return tp["per_iter_ms"] / 1e3


def measure_chip() -> dict:
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    n, d, layers = 65536, 128, (256, 128)
    dims = [d, *layers, 16]
    mults = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    rows = {}
    # epochs scale inversely with per-epoch time so every two-point delta
    # carries >= ~1.5 s of device time (the first cut at 96 epochs resolved
    # batch4096 to 46 "TFLOPS" — above chip peak, i.e. pure noise)
    for batch, epochs in ((512, 4000), (4096, 4000),
                          (65536 // sess.num_workers, 4000)):
        eps = _two_point_epoch_s(sess, n, d, layers, batch, epochs=epochs)
        steps = -(-(n // sess.num_workers) // batch)
        rows[f"batch{batch}"] = {
            "us_per_step": round(eps / steps * 1e6, 1),
            "mflop_per_step": round(6.0 * mults * batch / 1e6, 1),
            "achieved_tflops": round(6.0 * mults * batch * steps / eps / 1e12,
                                     2),
            "samples_per_sec": round(n / eps),
        }
    # the r5 compute-bound bench config
    nb, db, lb, bb = 65536, 512, (2048, 1024), 8192
    dimsb = [db, *lb, 16]
    multsb = sum(a * b for a, b in zip(dimsb[:-1], dimsb[1:]))
    eps = _two_point_epoch_s(sess, nb, db, lb, bb, epochs=150)
    steps = -(-(nb // sess.num_workers) // bb)
    rows["compute_bound_d512_2048x1024_b8192"] = {
        "us_per_step": round(eps / steps * 1e6, 1),
        "mflop_per_step": round(6.0 * multsb * bb / 1e6, 1),
        "achieved_tflops": round(6.0 * multsb * bb * steps / eps / 1e12, 2),
        "samples_per_sec": round(nb / eps),
    }
    return rows


def measure_mesh() -> dict:
    """Allreduce share on the 8-worker virtual CPU mesh (upper bound)."""
    import jax

    from harp_tpu.session import HarpSession

    w = min(8, len(jax.devices()))
    sess = HarpSession(num_workers=w, devices=jax.devices()[:w])
    n, d, layers, batch = 65536, 128, (256, 128), 512
    full = _two_point_epoch_s(sess, n, d, layers, batch, epochs=12)
    nops = _two_point_epoch_s(sess, n, d, layers, batch, epochs=12,
                              ablate_allreduce=True)
    return {
        "workers": w,
        "epoch_ms_full": round(full * 1e3, 2),
        "epoch_ms_no_allreduce": round(nops * 1e3, 2),
        "allreduce_share_pct_upper_bound": round(
            100 * max(full - nops, 0.0) / full, 1),
    }


def main() -> None:
    if "--mesh" in sys.argv:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps({"mesh": measure_mesh()}))
    else:
        print(json.dumps({"chip": measure_chip()}))


if __name__ == "__main__":
    main()
