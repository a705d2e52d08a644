"""How the harness drives the ``ccd-k100`` configuration through the
program: ``CCD.prepare`` once, then ``CCD.train_prepared`` per call, as
``python -m harp_tpu.run ccd`` does (its ``fit_prepared`` is
``train_prepared`` plus the factor fetch; the fetch belongs to the
comparison, so it is ``finalize`` here). A call starts from the factors the
call before returned: they are the last two entries of the prepared state's
placed arrays. Only these entry points are called: a program without them
fails at once with an ``AttributeError``."""

from __future__ import annotations

import numpy as np

from harp_tpu.models import ccd
from harp_tpu.session import HarpSession

FIELDS = ("rank", "lam", "inner_iterations")


class Driver:
    quality = "rmse"
    quality_scale = 1.0

    def __init__(self, config: dict, traffic: dict, data: dict, chips: int,
                 overrides: dict | None = None):
        fields = {k: config[k] for k in FIELDS}
        fields.update(overrides or {})
        self.session = HarpSession(num_workers=chips)
        self.model = ccd.CCD(self.session, ccd.CCDConfig(
            outer_iterations=int(traffic["epochs_per_call"]), **fields))
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
        return self._state[1][-2:]

    def call(self, factors):
        """One training call: ``epochs_per_call`` outer iterations from
        ``factors``. Returns the new factors (on the device) and each
        iteration's RMSE over the rated cells."""
        key, placed, num_rows, num_cols = self._state
        u, v, rmse = self.model.train_prepared(
            (key, (*placed[:-2], *factors), num_rows, num_cols))
        return (u, v), rmse

    def finalize(self, factors) -> dict:
        _, _, num_rows, num_cols = self._state
        return {"U": np.asarray(factors[0])[:num_rows],
                "V": np.asarray(factors[1])[:num_cols]}

    def compiled_step(self) -> tuple:
        """``(layout chosen, the compiled program one call runs)``."""
        key, placed, _, _ = self._state
        return (self.model.last_layout_stats["layout"],
                self.model._fns[key].lower(*placed).compile())

    def free(self) -> None:
        self._state = self.model = self._data = None
