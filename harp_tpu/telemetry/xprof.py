"""On-demand xprof windows — profile a slow gang without restarting it.

A control-plane event (``events.send_collective``; Harp's
CollectiveMapper.sendEvent:645 residual) arms every rank to capture a
``jax.profiler`` trace covering the next N chunk boundaries into a per-rank
directory. The request rides the SAME authenticated host control plane the
gang already synchronizes events over, and start/stop happen strictly at
chunk boundaries — the traced step programs are untouched (the profiler
observes them; it does not change them), so the collective-budget manifest
stays pinned with a window open.

Two trigger paths:

* **embedded** — any rank calls :func:`request_xprof` at a boundary. The
  request is a COLLECTIVE host event: every rank calls it together (the
  SPMD host loops make that free), only the source's payload is delivered.
* **operator** — the run.py CLI cannot inject a collective event from
  outside the gang (the event plane is authenticated and gang-internal), so
  the controller ALSO polls a trigger FILE at every boundary:
  ``<telemetry-dir>/xprof_request.json`` containing ``{"steps": N}``
  (optional ``"dir"``). Drop the file while the job runs and every rank
  opens a window at its next boundary. Window start/stop is purely LOCAL
  (no collective), so ranks reaching the boundary on either side of the
  file write simply open their windows one boundary apart — no alignment
  hazard. Each rank consumes a given file content once (mtime+size token);
  rewrite the file to re-arm.

The training side installs an :class:`XprofController` as a StepLog
boundary hook (``run.py`` does this when telemetry is enabled).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Optional

XPROF_TAG = "harp.telemetry.xprof"


def start_trace(log_dir: str) -> None:
    """Open a ``jax.profiler`` capture into ``log_dir`` (a window spans host
    loop boundaries, so it is opened and closed by two calls). Every
    :func:`~harp_tpu.telemetry.host_spans.phase` inside it lies in the trace's
    ``/host:CPU`` plane under its own name."""
    import jax

    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    """Close the capture opened by :func:`start_trace`."""
    import jax

    jax.profiler.stop_trace()


def request_xprof(session, steps: int, directory: str, *,
                  source: int = 0) -> None:
    """Arm an N-boundary profiler window on every rank (COLLECTIVE: all
    ranks call together; the ``source`` rank's payload wins). The window
    opens at each rank's next chunk boundary."""
    session.send_event({"tag": XPROF_TAG, "steps": int(steps),
                        "dir": directory}, source=source)


class XprofController:
    """Boundary hook driving the per-rank profiler window.

    Polls the session event queue at every boundary; on an armed request,
    starts ``jax.profiler`` into ``<dir>/rank<r>/`` and stops it after the
    requested number of boundaries. Non-xprof events are re-enqueued
    untouched. One window at a time; a request arriving mid-window extends
    nothing and is dropped with a note (re-arm after the window closes).
    """

    def __init__(self, session, rank: Optional[int] = None,
                 trigger_path: Optional[str] = None,
                 default_dir: Optional[str] = None):
        self.session = session
        self.rank = (int(os.environ.get("HARP_PROCESS_ID", "0"))
                     if rank is None else rank)
        self.remaining = 0
        self.trace_dir: Optional[str] = None
        self.trigger_path = trigger_path
        self.default_dir = default_dir
        self._consumed_token = None
        if trigger_path:
            # a trigger file left over from a PREVIOUS run must not open a
            # window at boundary 1 of this one: only writes after startup arm
            try:
                st = os.stat(trigger_path)
                self._consumed_token = (st.st_mtime_ns, st.st_size)
            except OSError:
                pass

    def _poll_request(self) -> Optional[dict]:
        requeue = []
        found = None
        while True:
            ev = self.session.get_event()
            if ev is None:
                break
            payload = ev.payload
            if (isinstance(payload, dict)
                    and payload.get("tag") == XPROF_TAG and found is None):
                found = payload
            else:
                requeue.append(ev)
        if requeue:
            queue = self.session.open_events()[0]
            for ev in requeue:
                queue.put(ev)
        if found is None:
            found = self._poll_trigger_file()
        return found

    def _poll_trigger_file(self) -> Optional[dict]:
        """The operator path: a JSON trigger file next to the telemetry
        output (module docstring). Malformed content is reported once per
        write, never fatal — a typo must not kill a training gang."""
        if not self.trigger_path:
            return None
        try:
            st = os.stat(self.trigger_path)
        except OSError:
            return None
        token = (st.st_mtime_ns, st.st_size)
        if token == self._consumed_token:
            return None
        self._consumed_token = token
        try:
            with open(self.trigger_path) as f:
                req = json.load(f)
            steps = int(req["steps"])
        except (OSError, ValueError, KeyError, TypeError) as e:
            print(f"harp_tpu.telemetry: bad xprof trigger file "
                  f"{self.trigger_path}: {e}", file=sys.stderr, flush=True)
            return None
        out = req.get("dir") or self.default_dir
        if not out:
            print(f"harp_tpu.telemetry: xprof trigger file has no 'dir' and "
                  f"no default directory is configured",
                  file=sys.stderr, flush=True)
            return None
        return {"tag": XPROF_TAG, "steps": steps, "dir": out}

    def _start(self, req: dict) -> None:
        self.trace_dir = os.path.join(req["dir"], f"rank{self.rank}")
        os.makedirs(self.trace_dir, exist_ok=True)
        start_trace(self.trace_dir)
        self.remaining = max(1, int(req["steps"]))
        print(f"harp_tpu.telemetry: xprof window open (rank {self.rank}, "
              f"{self.remaining} boundaries) -> {self.trace_dir}",
              file=sys.stderr, flush=True)

    def _stop(self) -> None:
        stop_trace()
        print(f"harp_tpu.telemetry: xprof window closed (rank {self.rank}) "
              f"-> {self.trace_dir}", file=sys.stderr, flush=True)
        self.remaining = 0

    @property
    def tracing(self) -> bool:
        return self.remaining > 0

    def __call__(self, boundary_index: int, log=None) -> None:
        """Tick one chunk boundary (StepLog boundary-hook signature)."""
        if self.tracing:
            self.remaining -= 1
            if self.remaining == 0:
                self._stop()
        req = self._poll_request()
        if req is not None:
            if self.tracing:
                print("harp_tpu.telemetry: xprof request ignored — a window "
                      "is already open (re-arm after it closes)",
                      file=sys.stderr, flush=True)
            else:
                self._start(req)

    def close(self) -> None:
        """End-of-job safety: a window left open past the last boundary is
        closed so the trace file is complete."""
        if self.tracing:
            self._stop()
