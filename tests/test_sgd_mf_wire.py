"""Which wire the dense SGD-MF hops ride to the right neighbour.

On the TPU backend, with the fused hop kernel live, a ring of more than one
worker, one slice and an unquantized wire, the kernel sends each finished
column tile of H itself (``"in_kernel"``) unless ``fused_dma=False``
asks for the ppermute schedule; ``fused_dma=True`` sends through the
ring-DMA engine's own hop wherever the kernel cannot (``"ring_dma"``);
everything else rides ``ppermute``, and a ring of one has no wire
(``"none"``). ``last_layout_stats["ring_hop"]`` says which, and the
``sgd_mf.ring.<wire>`` counters count it per traced hop body. A CPU run
has no TPU backend, so these tests patch its two predicates and only
trace the program (remote DMA has no CPU lowering);
the kernel's own sends are checked on four chips (``chip_smoke.py``
``multichip_ring``) and compiled for a described v5e
(``tests/test_scopes.py``).
"""

import json
import os

import jax
import numpy as np
import pytest

from harp_tpu.models import sgd_mf
from harp_tpu.ops import pallas_kernels, ring_dma
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIRES = ("in_kernel", "ring_dma", "ppermute")


@pytest.fixture(scope="module")
def sessions():
    return {w: HarpSession(num_workers=w) for w in (1, 4)}


def _expected(tpu, workers, slices, quant, fused_dma):
    if workers == 1:
        return "none"
    if tpu and slices == 1 and quant is None and fused_dma is not False:
        return "in_kernel"
    return "ring_dma" if fused_dma and quant is None else "ppermute"


def _ring_counters():
    counters = dict(metrics.DEFAULT.counters)
    return {w: counters.get("sgd_mf.ring." + w, 0) for w in WIRES}


@pytest.mark.parametrize("fused_dma", [None, True, False],
                         ids=["default", "fused", "ppermute"])
@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("slices", [1, 2])
@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
def test_the_program_picks_the_wire(monkeypatch, sessions, tpu, workers,
                                    slices, quant, fused_dma):
    if tpu:
        monkeypatch.setattr(ring_dma, "use_ring_dma", lambda: True)
        monkeypatch.setattr(pallas_kernels, "use_dense_mf_pallas",
                            lambda *shape: True)
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 64, size=300)
    cols = rng.integers(0, 48, size=300)
    vals = rng.normal(size=300).astype(np.float32)
    model = sgd_mf.SGDMF(sessions[workers], sgd_mf.SGDMFConfig(
        rank=8, epochs=1, minibatches_per_hop=2, num_slices=slices,
        layout="dense", quant=quant, fused_dma=fused_dma))
    layout, data, w0, h0, meta = model.prepare(rows, cols, vals, 64, 48)
    want = _expected(tpu, workers, slices, quant, fused_dma)
    assert model.last_layout_stats["fused_hop"] is tpu
    assert model.last_layout_stats["ring_hop"] == want

    before = _ring_counters()
    key = model._program(layout, 2, 1, meta[6])
    model._compiled[key].trace(*data, w0, h0)     # traced, never lowered
    traced = {w: n - before[w] for w, n in _ring_counters().items()}
    if want == "none":
        assert traced == dict.fromkeys(WIRES, 0)
    else:
        assert traced[want] >= 1
        assert {w: n for w, n in traced.items() if w != want} == {
            w: 0 for w in WIRES if w != want}


def test_the_default_wire_traces_as_the_budgets_ppermute_row():
    """Off the TPU the default configuration's program is the ppermute
    schedule that ``tools/collective_budget.json`` pins for ``sgd_mf_dense``
    (a hop's H block as one ``ppermute``, the RMSE's two ``psum``s), not
    the ring-DMA engine's tagged hop."""
    from tools.jaxlint import checkers_jaxpr, trace_targets

    trace_targets.ensure_cpu_mesh()
    fn, args = trace_targets._sgd_mf(
        fused_dma=sgd_mf.SGDMFConfig().fused_dma)()
    counts, dtype_bad, nbytes = {}, [], {}
    checkers_jaxpr._walk(jax.make_jaxpr(fn)(*args).jaxpr, counts, dtype_bad,
                         nbytes)
    with open(os.path.join(REPO, checkers_jaxpr.BUDGET_FILE)) as f:
        row = json.load(f)["targets"]["sgd_mf_dense"]
    assert counts == row["collectives"] == {"ppermute": 1, "psum": 2}
    assert nbytes == row["bytes_by_kind"]
    assert dtype_bad == []
