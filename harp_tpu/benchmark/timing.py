"""Shared two-point timing protocol (r5 measurement-rigor pass).

Every program call costs a constant besides its iterations (dispatch, the
result fetch). On the machine the r5 numbers were taken on that constant
was 0.1-0.4 s and DRIFTED within a process, so single-call wall clocks were
meaningless and sequential lo-then-hi runs biased the delta. On a directly
attached chip the constant is small and ``block_until_ready`` blocks
(chip_smoke.py prints one observation of it); whether this protocol is
still needed is ROADMAP D2. The protocol every harness uses (bench.py,
lda_stages, nn_budget):

* compile the same workload at a LOW and a HIGH in-program iteration count;
* run reps ALTERNATING lo/hi so drift hits both medians equally;
* rate = d(wall-median) / d(iters) — the per-call constant cancels;
* guard the noise floor: a non-positive delta falls back to the wall rate of
  the high count (the workload is all fixed cost at this size) and is
  visible in the spread columns.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, Dict


def two_point_timers(timer_lo: Callable[[], None],
                     timer_hi: Callable[[], None],
                     lo: int, hi: int, units_per_iter: float,
                     reps: int = 3) -> Dict:
    """Measure prepared (compiled + warmed) timers at two iteration counts.

    Each timer runs ONE dispatch and blocks until results are real on host.
    Returns rate (units/s), per_iter_ms, fixed_dispatch_s, spread_pct and the
    raw samples."""
    if hi <= lo:
        raise ValueError(f"two-point timing needs hi > lo, got lo={lo} "
                         f"hi={hi} (pick a larger iteration budget)")
    s_lo, s_hi = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        timer_lo()
        s_lo.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        timer_hi()
        s_hi.append(time.perf_counter() - t0)
    med_lo, med_hi = statistics.median(s_lo), statistics.median(s_hi)
    delta = med_hi - med_lo
    per_iter = delta / (hi - lo)
    if per_iter <= 0:  # noise floor: the workload is all fixed cost
        per_iter = max(med_hi / hi, 1e-9)

    def _jitter(s):
        # spread of the two FASTEST runs: bounds steady-state noise without
        # letting one slow outlier (a host hiccup / cold first call)
        # declare a cleanly-resolved row unresolved
        a = sorted(s)
        return a[1] - a[0] if len(a) > 1 else 0.0

    jitter = max(_jitter(s_hi), _jitter(s_lo))
    return {
        "rate": units_per_iter / per_iter,
        "per_iter_ms": round(per_iter * 1e3, 4),
        "fixed_dispatch_s": round(max(med_lo - lo * per_iter, 0.0), 3),
        "spread_pct": round(100 * (max(s_hi) - min(s_hi)) / med_hi, 1),
        "delta_s": round(delta, 4),
        # the delta must stand clear of the per-sample jitter or the rate is
        # noise wearing a number (the first NN budget run "measured" 342
        # TFLOPS — above chip peak — from a 40 ms delta): callers pick
        # iteration counts so the delta carries seconds of device time
        "low_resolution": bool(delta < 2 * jitter),
        "iters_lo_hi": [lo, hi],
        "samples_s": {"lo": [round(t, 4) for t in s_lo],
                      "hi": [round(t, 4) for t in s_hi]},
    }


def two_point(build: Callable[[int], Callable[[], None]], lo: int, hi: int,
              units_per_iter: float, reps: int = 3) -> Dict:
    """build(n) compiles + warms the workload at n in-program iterations and
    returns its one-dispatch timer; see :func:`two_point_timers`."""
    return two_point_timers(build(lo), build(hi), lo, hi, units_per_iter,
                            reps)
