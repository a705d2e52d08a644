"""How the harness drives the ``wdamds-d3`` configuration through the
program: the two matrices made on the host as a distance file and a weight
file would hold them (Euclidean distances between the cell's points in
float32, as ``python -m harp_tpu.run mds`` makes them; weight 1 up to
``distance_cut``, else 0), ``WDAMDS.prepare`` once, then
``WDAMDS.train_prepared`` per call. A call starts from the carry the call
before returned, the embedding and the job's iteration count: they are the
last two entries of the prepared state's placed arrays. ``finalize`` is the
program's own way out, the centred embedding. Only these entry points are
called: a program without them fails at once with an ``AttributeError``."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from harp_tpu.models import mds
from harp_tpu.session import HarpSession

# the configuration's names of MDSConfig's fields
FIELDS = ("target_dim", "cg_iters", "alpha", "level_iterations", "t_floor")
_ROWS = 256                 # rows of a block of the mask's pass


class Driver:
    quality = "stress"
    quality_scale = 1.0

    def __init__(self, config: dict, traffic: dict, data: dict, chips: int,
                 overrides: dict | None = None):
        fields = {k: config[k] for k in FIELDS}
        fields.update(overrides or {})
        fields["dim"] = fields.pop("target_dim")
        self.session = HarpSession(num_workers=chips)
        self.model = mds.WDAMDS(self.session, mds.MDSConfig(
            iterations=int(traffic["epochs_per_call"]), **fields))
        self._data = data
        self._cut = np.float32(config["distance_cut"])
        n = len(data["points"])
        # the target distances one iteration fits
        self.samples_per_epoch = n * (n - 1)
        self._state = None

    def prepare(self) -> None:
        dist = mds.distance_matrix(self._data["points"])
        # 0 and 1 are exact in bfloat16: the mask is made in the type the
        # configuration states, by row blocks, and stored as handed over
        weights = np.empty(dist.shape, jnp.bfloat16)
        for lo in range(0, len(dist), _ROWS):
            weights[lo:lo + _ROWS] = dist[lo:lo + _ROWS] <= self._cut
        self._state = self.model.prepare(dist, weights,
                                         seed=self._data["init_seed"])

    def initial(self):
        return self._state[1][-2:]

    def call(self, carry):
        """One training call: ``epochs_per_call`` iterations from ``carry``.
        Returns the new carry (on the device) and each iteration's
        normalised stress."""
        key, placed = self._state
        return self.model.train_prepared((key, (*placed[:-2], *carry)))

    def finalize(self, carry) -> dict:
        return {"X": self.model.embedding(carry)}

    def compiled_step(self) -> tuple:
        """``(layout chosen, the compiled program one call runs)``."""
        key, placed = self._state
        stats = self.model.last_layout_stats
        return (f"dense rows, weights {stats['weights_dtype']}, "
                f"{stats['kernel']}",
                self.model._fns[key].lower(*placed).compile())

    def free(self) -> None:
        self._state = self.model = self._data = None
