"""How the harness drives the ``lda-k1000`` configuration through the
program: ``LDA.prepare`` once (the CSR corpus and the seed's first model,
which it lays out as the stream), then ``LDA.train_prepared`` with ``epochs_per_call``
sweeps per call, each call from the state the call before returned.
``finalize`` is the program's own way out, ``LDA.parameters``. Only these
entry points are called, and they are looked up first: a program without
them fails at once with an ``AttributeError``."""

from __future__ import annotations

from harp_tpu.models import lda
from harp_tpu.session import HarpSession

# the configuration's names of LDAConfig's fields
FIELDS = ("num_topics", "vocab", "alpha", "beta", "groups_per_sweep",
          "method")


class Driver:
    quality = "neg_loglik_per_token"
    quality_scale = 1.0

    def __init__(self, config: dict, traffic: dict, data: dict, chips: int,
                 overrides: dict | None = None):
        # the program's entry points, bound before anything else: a program
        # without them fails here, at once, with an AttributeError
        self._train = lda.LDA.train_prepared
        self._parameters = lda.LDA.parameters
        fields = {k: config[k] for k in FIELDS}
        fields.update(overrides or {})
        self.session = HarpSession(num_workers=chips)
        self._per_call = int(traffic["epochs_per_call"])
        self.model = lda.LDA(self.session, lda.LDAConfig(
            epochs=self._per_call, **fields))
        self._data = data
        self.samples_per_epoch = int(data["samples_per_epoch"])
        self._state = None

    def prepare(self) -> None:
        data = self._data
        self._state = self.model.prepare((data["doc_ptr"], data["words"]),
                                         seed=data["init_seed"])

    def initial(self):
        return self._state

    def call(self, state):
        """One training call: ``epochs_per_call`` sweeps. Returns the new
        state (on the device) and each sweep's quality."""
        return self._train(self.model, state, self._per_call)

    def finalize(self, state) -> dict:
        word_topic, doc_topic, _ = self._parameters(self.model, state)
        return {"word_topic": word_topic, "doc_topic": doc_topic}

    def compiled_step(self) -> tuple:
        """``(layout chosen, the compiled program one call runs)``."""
        geom, _, static, chain = self._state
        key = self.model._stream_program(geom, self._per_call)
        stats = self.model.last_layout_stats
        return (f"token stream, {geom.groups} groups, tiles of "
                f"{stats['token_tile']}, {stats['kernel']}, count write "
                f"{stats['count_write']}",
                self.model._fns[key].lower(*static, *chain).compile())

    def free(self) -> None:
        self._state = self.model = self._data = None
