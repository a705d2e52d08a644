"""The cell ``lda-k1000.nytimes-100m`` through the harness on the CPU at 200
documents over 2,000 words: ``correct`` as the configuration is written,
false under its bfloat16 control and a state returned unchanged; the
generator's shapes, structure and determinism; the work function against a
hand count; the two kernel readers on a reduction they can and cannot read;
an ``LDA`` without ``train_prepared`` fails the driver at once. Every entry
is found by name."""

import hashlib
import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, harness, traffic
from tests.benchmark import tiny
from tests.benchmark.test_als_cell import _rewrite
from tests.benchmark.test_faults import _patch_driver

CELL = "lda-k1000.nytimes-100m"
CONFIG = "lda-k1000"
READERS = ("lda_sample_ms", "lda_sample_roofline_share")
# tiny traffic: 200 documents over 2,000 words (57 k tokens at the seed
# below); the topics, the laws and every width stay
_DOCS, _VOCAB = 200, 2000
_SEED = tiny.SEED + 30
# the configuration's own limits on the four state numbers: the running
# sum's order is stated, so the program draws as the reference bit for bit
# at any size and reads 0 on them; the bfloat16 control reads 0.93-0.95
# (step1_diff) and 3.0e-3 or more (the norm gaps) here. The two sides' sums
# of the quality part at 1.3e-6 to 1.7e-6 at this size (6e-8 to 2e-7 at the
# cell's), the control at 6.4e-4 or more: the tiny cell holds 1e-4
with open(os.path.join(tiny.BENCH, "configs", CONFIG + ".json")) as _fh:
    _LIMITS = {**json.load(_fh)["limits"], "quality_gap": 1e-4}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("bench")))

    def cut(doc):
        doc["params"].update(docs=_DOCS, vocab=_VOCAB)
        doc["target"]["at_most"] = 1e9       # every job ends at its first call

    _rewrite(os.path.join(root, "benchmark", "workloads", CELL + ".json"), cut)
    _rewrite(os.path.join(root, "benchmark", "configs", CONFIG + ".json"),
             lambda doc: doc.update(limits=_LIMITS, vocab=_VOCAB))
    return root


def _run(tree, seed=_SEED, trace=False):
    return harness.run_cell(CELL, seed, 0.3, trace,
                            require_accelerator=False, root=tree)


def test_the_cell_is_in_the_manifest_as_specified():
    cell = harness.find_cell(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic["generator"] == "planted_corpus"
    assert cell.traffic["params"] == {
        "docs": 299_752, "vocab": 102_660, "mean_doc_len": 332,
        "len_sigma": 0.75, "topics": 1000, "doc_alpha": 0.1,
        "topic_concentration": 2000, "word_zipf": 1.0, "structure_seed": 11}
    assert cell.traffic["epochs_per_call"] == 1
    assert cell.traffic["target"]["quality"] == "neg_loglik_per_token"
    job = cell.traffic["max_epochs"] // 3
    assert job >= 1 and cell.traffic["max_epochs"] == 3 * job
    c = cell.config
    assert c["model"] == "harp_tpu.models.lda.LDA" and c["architecture"] is None
    assert (c["num_topics"], c["vocab"], c["alpha"], c["beta"],
            c["method"]) == (1000, 102_660, 0.05, 0.01, "cgs")
    assert c["groups_per_sweep"] == 32
    assert c["device_op_names"] == {"sample": ["lda_sample"]}
    assert c["control"] == {**c["control"], "kind": "reference",
                            "products": "bfloat16"}
    assert c["reduced"] == [] and len(c["assumed"]) >= 7
    assert "fmix32" in c["precision"] and "0x9E3779B9" in c["precision"]
    assert "o_s = o_(s-1) + t_(s-1)" in c["precision"]      # the sum's order
    assert len(c["source"]) <= 200 and "ml/java lda" in c["source"]
    assert cell.part("driver").FIELDS == (
        "num_topics", "vocab", "alpha", "beta", "groups_per_sweep", "method")
    assert set(cell.limits) <= set(compare.NUMBERS) and cell.limits
    entries = {group: [e for e in cell.manifest[group] if e["name"] in names]
               for group, names in (("configs", {CONFIG}),
                                    ("workloads", {CELL}),
                                    ("per_layer", set(READERS)))}
    assert [len(v) for v in entries.values()] == [1, 1, 2]
    assert entries["configs"][0]["source"] == c["source"]
    assert entries["configs"][0]["reduced"] == []
    assert entries["workloads"][0]["traffic"] == "nytimes-100m"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {*READERS, "step_mfu", "update_roofline_share",
            "device_idle_share", "peak_hbm_gib", "epochs_to_target",
            "data_prep_s", "backend_compile_s"} == names
    for m in entries["per_layer"]:
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert (m["layer"], m["moves"]) == ("models / kernels",
                                            "samples_per_s")
    for other in cell.manifest["workloads"]:
        if other["name"] != CELL:
            assert not set(READERS) & {m["name"] for m in harness.find_cell(
                other["name"]).metrics("per_layer")}
    assert sum(w["chips"] == 4 for w in cell.manifest["workloads"]) == 1


def test_the_kernel_bears_the_name_the_configuration_lists():
    from harp_tpu.ops import lda_kernels

    names = harness.find_cell(CELL).config["device_op_names"]
    assert names == {"sample": [lda_kernels.NAME]}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(tiny.BENCH, "configs", CONFIG + ".reference.py")
    with open(path) as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "harp_tpu" in line]


# -- the generator ------------------------------------------------------------ #

_GEN = {"docs": 64, "vocab": 300, "mean_doc_len": 332, "len_sigma": 0.75,
        "topics": 20, "doc_alpha": 0.1, "topic_concentration": 2000,
        "word_zipf": 1.0, "structure_seed": 11}


def _generate(seed=tiny.SEED, **params):
    return traffic.generate({"generator": "planted_corpus",
                             "params": {**_GEN, **params}}, {}, seed)


def test_the_generator_gives_csr_and_one_corpus_a_seed():
    a, b = _generate(), _generate()
    doc_ptr, words = a["doc_ptr"], a["words"]
    assert doc_ptr.dtype == np.int64 and words.dtype == np.int32
    assert doc_ptr.shape == (65,) and doc_ptr[0] == 0
    assert doc_ptr[-1] == len(words) == a["samples_per_epoch"]
    lens = np.diff(doc_ptr)
    assert lens.min() >= 8 and lens.max() <= 10_000
    assert 0 <= words.min() and words.max() < 300
    assert np.array_equal(words, b["words"])
    assert hashlib.sha256(words.tobytes()).hexdigest() == _DIGEST
    c = _generate(seed=tiny.SEED + 1)
    assert not np.array_equal(doc_ptr, c["doc_ptr"])
    big = _generate(seed=2 ** 32 + 39, docs=4000)
    lens = np.diff(big["doc_ptr"])
    assert 300 < lens.mean() < 365 and 0.65 < np.log(lens).std() < 0.8


_DIGEST = ("70086e157899c6a457344d2027dbbb49"
           "74753b946668f033c5b6720469e2f3d9")


def test_the_structure_is_the_stated_one():
    """Each topic a Dirichlet around the Zipf law: the words' total mass
    over the topics follows it, the heaviest word near 1 / H(300)."""
    path = os.path.join(tiny.BENCH, "generators", "planted_corpus.py")
    module = harness.load_module(path)
    cdf = module._topic_words(_GEN)
    assert cdf.shape == (20, 300) and np.allclose(cdf[:, -1], 1.0)
    mass = np.diff(cdf, axis=1, prepend=0.0).mean(axis=0)
    harmonic = np.sum(1.0 / np.arange(1, 301))
    assert mass.max() == pytest.approx(1.0 / harmonic, rel=0.1)
    assert np.array_equal(cdf, module._topic_words(_GEN))


# -- the cell on the CPU ---------------------------------------------------- #

def test_an_untraced_run_is_correct(tree):
    line = json.loads(json.dumps(_run(tree)))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "time_to_target_s",
                                    "call_ms_p95", "setup_s"}
    assert line["window"]["epochs_to_target"] == [1]
    assert line["window"]["compiles_in_window"] == 0
    held = {k for k, v in line["compared"].items() if v["limit"] is not None}
    assert held == set(_LIMITS) == set(compare.NUMBERS)
    for name, limit in _LIMITS.items():
        assert line["compared"][name]["value"] < limit / 3, name
        if name != "quality_gap":
            assert line["compared"][name]["value"] == 0.0, name


def test_a_traced_run_reads_the_old_metrics_and_no_kernel_it_cannot_see(
        tree, monkeypatch):
    """The recorded trace is K-means': no operation of it bears the name the
    configuration lists, so the two readers report nothing, never 0."""
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    line = _run(tree, trace=True)
    assert line["correct"] is True
    assert {"step_mfu", "update_roofline_share", "device_idle_share",
            "peak_hbm_gib", "epochs_to_target", "data_prep_s",
            "backend_compile_s"} == set(line["metrics"])


def test_the_bfloat16_control_fails_every_limit_the_tiny_cell_holds(tree):
    cell = harness.find_cell(CELL, tree)
    data = harness.make_data(cell, _SEED + 1)
    first, reference = harness.follow_reference(cell, data)
    _, control = harness.follow_reference(cell, data, products=jnp.bfloat16)
    read = compare.numbers(first, control, reference)
    for name, limit in _LIMITS.items():
        assert read[name] > 3 * limit, (name, read[name])


def test_fault_a_state_returned_unchanged(tree, monkeypatch):
    def unchanged(call):
        def broken(self, state):
            _, quality = call(self, state)
            return state, quality
        return broken

    _patch_driver(monkeypatch, tree, CELL, call=unchanged)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["step1_diff"]["value"] == pytest.approx(1.0)


def test_fault_the_first_groups_count_write_dropped(tree, monkeypatch):
    """Every sweep's first group draws, and its changes to the three count
    tables are thrown away: the chain runs on counts its z does not hold."""
    from harp_tpu.ops import lda_kernels

    twin = lda_kernels.sample_xla

    def dropped(meta, *args, **kwargs):
        zn, wt, dt, dnk, moved = twin(meta, *args, **kwargs)
        keep = meta[2] != 0                  # the group at document row 0
        return (zn, jnp.where(keep, wt, args[6]), jnp.where(keep, dt, args[7]),
                jnp.where(keep, dnk, 0), moved)

    monkeypatch.setattr(lda_kernels, "sample_xla", dropped)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["step1_diff"]["value"] > 3 * _LIMITS["step1_diff"]


def test_fault_fully_parallel_draws(tree, monkeypatch):
    """One group a sweep (every token against the sweep's first counts) in
    place of the stated 32."""
    def one_group(init):
        def broken(self, config, cell_traffic, data, chips, overrides=None):
            init(self, config, cell_traffic, data, chips,
                 {"groups_per_sweep": 1})
        return broken

    _patch_driver(monkeypatch, tree, CELL, __init__=one_group)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["step1_diff"]["value"] > 3 * _LIMITS["step1_diff"]


def test_a_program_without_train_prepared_fails_the_driver_at_once(
        tree, monkeypatch):
    """An ``LDA`` with no ``train_prepared`` (the one-call ``fit`` API): the
    driver ends as it is made, before anything is laid out or placed."""
    from harp_tpu.models import lda

    cell = harness.find_cell(CELL, tree)
    data = {**_generate(docs=16), "init_seed": 3}
    monkeypatch.delattr(lda.LDA, "train_prepared")
    with pytest.raises(AttributeError, match="train_prepared"):
        cell.part("driver").Driver(cell.config, cell.traffic, data, 1)


def test_work_against_a_hand_count():
    cell = harness.find_cell(CELL)
    work = cell.part("work").work(cell.config, cell.traffic)
    n, k, w, d = 99_517_664, 1000, 102_660, 299_752
    assert n == round(299_752 * 332)
    assert work["samples_per_epoch"] == n
    assert work["flops_per_epoch"] == 4 * n * k
    assert work["bytes_per_epoch"] == 16 * n + 8 * (w + d) * k
    assert work["sample_flops_per_epoch"] == work["flops_per_epoch"]
    assert work["sample_bytes_per_epoch"] == work["bytes_per_epoch"]


def _reader(name):
    cell = harness.find_cell(CELL)
    return cell, harness.load_module(os.path.join(
        cell.bench_dir, "metrics", name + ".py"))


def _ctx(cell, device_ops, epochs=10):
    return types.SimpleNamespace(
        cell=cell, window=types.SimpleNamespace(epochs=epochs),
        trace=types.SimpleNamespace(device_ops=device_ops),
        work=cell.part("work").work(cell.config, cell.traffic),
        peak=lambda: harness.peak_of(cell.bench_dir, "TPU v5 lite"))


def test_the_readers_sum_the_kernels_events_by_its_fixed_name():
    cell, _ = _reader(READERS[0])
    ops = [("fusion.7", 0.3), ("lda_sample.8", 9.0), ("lda_sample.9", 1.5),
           ("lda_sample_like.2", 5.0), ("custom-call.29", 0.2)]
    read = {name: _reader(name)[1].read(_ctx(cell, ops)) for name in READERS}
    assert read["lda_sample_ms"] == pytest.approx(1050.0)
    # the bytes bound it: 4.81e9 bytes at the HBM's peak against 3.98e11
    # operations at the bfloat16 peak
    work = cell.part("work").work(cell.config, cell.traffic)
    peak = harness.peak_of(cell.bench_dir, "TPU v5 lite")
    least = max(work["sample_flops_per_epoch"] / peak["bf16_flops_per_s"],
                work["sample_bytes_per_epoch"] / peak["hbm_bytes_per_s"])
    assert read["lda_sample_roofline_share"] == pytest.approx(
        100 * least / 1.050, rel=1e-9)


@pytest.mark.parametrize("ops", [[], [("fusion.19", 1.0), ("copy.3", 0.5)]])
def test_the_readers_report_nothing_where_no_kernel_ran(ops):
    cell, _ = _reader(READERS[0])
    ctx = _ctx(cell, ops)
    assert [_reader(name)[1].read(ctx) for name in READERS] == [None] * 2
    ctx.trace = None
    assert [_reader(name)[1].read(ctx) for name in READERS] == [None] * 2
