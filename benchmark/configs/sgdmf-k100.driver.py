"""How the harness drives the ``sgdmf-k100`` configuration through the
program: ``SGDMF.prepare`` once, then ``SGDMF.train_prepared`` per call, as
``python -m harp_tpu.run sgd_mf`` does (its ``fit_prepared`` is
``train_prepared`` plus the factor fetch; the fetch belongs to the
comparison, so it is ``finalize`` here)."""

from __future__ import annotations

from harp_tpu.models import sgd_mf
from harp_tpu.session import HarpSession

FIELDS = ("rank", "lam", "lr", "minibatches_per_hop", "num_slices", "layout")


class Driver:
    quality = "rmse"
    quality_scale = 1.0

    def __init__(self, config: dict, traffic: dict, data: dict, chips: int,
                 overrides: dict | None = None):
        fields = {k: config[k] for k in FIELDS}
        fields.update(overrides or {})
        self.session = HarpSession(num_workers=chips)
        self.model = sgd_mf.SGDMF(self.session, sgd_mf.SGDMFConfig(
            epochs=int(traffic["epochs_per_call"]), **fields))
        self._data = data
        self.samples_per_epoch = int(data["samples_per_epoch"])
        self._state = None

    def prepare(self) -> None:
        d = self._data
        self._state = self.model.prepare(
            d["rows"], d["cols"], d["vals"], d["num_rows"], d["num_cols"],
            seed=d["init_seed"])
        dropped = self.model.last_layout_stats["duplicates_dropped"]
        if dropped:
            raise ValueError(f"the program dropped {dropped} duplicate "
                             "ratings: the generator's pairs are distinct")

    def initial(self):
        return self._state[2], self._state[3]

    def call(self, factors):
        """One training call: ``epochs_per_call`` epochs from ``factors``.
        Returns the new factors (on the device) and the per-epoch RMSE."""
        layout, data, _, _, meta = self._state
        w, h, rmse = self.model.train_prepared(
            (layout, data, factors[0], factors[1], meta))
        return (w, h), rmse

    def finalize(self, factors) -> dict:
        w, h = self.model._finalize(factors[0], factors[1], self._state[4])
        return {"W": w, "H": h}

    def compiled_step(self) -> tuple:
        """``(layout chosen, the compiled program one call runs)``."""
        layout, data, w0, h0, meta = self._state
        key = self.model._program(
            layout, self.model.config.minibatches_per_hop,
            self.model.config.epochs, meta[6])
        return layout, self.model._compiled[key].lower(*data, w0, h0).compile()

    def free(self) -> None:
        self._state = self.model = self._data = None
