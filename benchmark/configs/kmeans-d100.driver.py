"""How the harness drives the ``kmeans-d100`` configuration through the
program: ``KMeans.prepare`` once, then ``KMeans.fit_prepared`` per call, as
``python -m harp_tpu.run kmeans`` does (``harp_tpu/run.py``)."""

from __future__ import annotations

import numpy as np

from harp_tpu.models import kmeans
from harp_tpu.session import HarpSession

FIELDS = ("num_centroids", "dim", "comm", "compute_dtype", "lane_pad")


class Driver:
    quality = "cost_over_planted"

    def __init__(self, config: dict, traffic: dict, data: dict, chips: int,
                 overrides: dict | None = None):
        fields = {k: config[k] for k in FIELDS}
        fields.update(overrides or {})
        self.session = HarpSession(num_workers=chips)
        self.model = kmeans.KMeans(self.session, kmeans.KMeansConfig(
            iterations=int(traffic["epochs_per_call"]), **fields))
        self._data = data
        self.samples_per_epoch = int(data["samples_per_epoch"])
        # the program reports a cost; the cell's target is its ratio to the
        # generating model's cost on this sample
        self.quality_scale = 1.0 / float(data["planted_cost"])
        self._points = self._first = None

    def prepare(self) -> None:
        self._points, self._first = self.model.prepare(
            self._data["points"], self._data["centroids0"])

    def initial(self):
        return self._first

    def call(self, centroids):
        """One training call: ``epochs_per_call`` iterations. Returns the new
        model and the per-epoch costs, still on the device."""
        return self.model.fit_prepared(self._points, centroids)

    def finalize(self, centroids) -> dict:
        return {"centroids": np.asarray(centroids)}

    def compiled_step(self) -> tuple:
        """``(layout chosen, the compiled program one call runs)``."""
        layout = ("dense points, lane-padded" if self.model.config.lane_pad
                  else "dense points")
        return layout, self.model._fit.lower(
            self._points, self._first).compile()

    def free(self) -> None:
        self._points = self._first = self.model = self._data = None
