"""How the harness drives the ``emgmm-k100d100`` configuration through the
program: ``EMGMM.prepare`` once (the points and the traffic's first model),
then ``EMGMM.train_prepared`` with ``epochs_per_call`` iterations per call,
each call from the state the call before returned. ``finalize`` is the
program's own way out, ``EMGMM.parameters``. Only these entry points are
called: a program without them fails at once with an ``AttributeError``."""

from __future__ import annotations

from harp_tpu.models import em
from harp_tpu.session import HarpSession

# the configuration's names of EMConfig's fields
FIELDS = ("num_components", "reg")


class Driver:
    quality = "neg_mean_loglik"
    quality_scale = 1.0

    def __init__(self, config: dict, traffic: dict, data: dict, chips: int,
                 overrides: dict | None = None):
        fields = {k: config[k] for k in FIELDS}
        fields.update(overrides or {})
        self.session = HarpSession(num_workers=chips)
        self._per_call = int(traffic["epochs_per_call"])
        self.model = em.EMGMM(self.session, em.EMConfig(
            iterations=self._per_call, **fields))
        self._data = data
        self.samples_per_epoch = int(data["samples_per_epoch"])
        self._state = None

    def prepare(self) -> None:
        data = self._data
        self._state = self.model.prepare(data["points"], data["weights0"],
                                         data["means0"], data["covs0"])

    def initial(self):
        return self._state

    def call(self, state):
        """One training call: ``epochs_per_call`` EM iterations. Returns the
        new state (on the device) and each iteration's quality."""
        return self.model.train_prepared(state, self._per_call)

    def finalize(self, state) -> dict:
        weights, means, covs = self.model.parameters(state)
        return {"weights": weights, "means": means, "covs": covs}

    def compiled_step(self) -> tuple:
        """``(layout chosen, the compiled program one call runs)``."""
        geom, placed = self._state
        key = self.model._program(geom, self._per_call)
        stats = self.model.last_layout_stats
        return (f"dense points, lane-padded to {geom.d_store}, "
                f"{stats['kernel']}",
                self.model._fns[key].lower(*placed).compile())

    def free(self) -> None:
        self._state = self.model = self._data = None
