"""jaxlint (tools/jaxlint) — tier-1.

Three layers, mirroring tests/test_check_claims.py's contract style:

* fixture snippets with KNOWN violations assert the exact finding codes
  each checker raises (and that the clean twin of each snippet is silent);
* the repo itself must lint clean (this is the tier-1 wiring — a new
  violation anywhere in harp_tpu/ fails the suite, so DOTS_PASSED captures
  the lint exactly like the scatter lint it absorbed);
* the allowlist contract: justifications are mandatory, stale entries fail;
* the jaxpr engine: traced collective budgets must match the committed
  tools/collective_budget.json, and drift is detected loudly.
"""

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.jaxlint import checkers_jaxpr  # noqa: E402
from tools.jaxlint import checkers_ast as ca  # noqa: E402
from tools.jaxlint.allowlist import ALLOWLIST  # noqa: E402
from tools.jaxlint.core import (Finding, apply_allowlist,  # noqa: E402
                                run_ast_checkers, validate_allowlist)


def _run(checker, src, rel="harp_tpu/models/fake.py"):
    return checker(ast.parse(src), rel, src)


def _codes(findings):
    return [f.code for f in findings]


# -- JL101 collective-divergence -------------------------------------------

def test_collective_in_rank_branch_is_flagged():
    src = (
        "def step(x):\n"
        "    wid = lax_ops.worker_id()\n"
        "    if wid == 0:\n"
        "        x = jax.lax.psum(x, 'workers')\n"
        "    return x\n")
    got = _run(ca.check_collective_divergence, src)
    assert _codes(got) == ["JL101"]
    assert got[0].func == "step" and "psum" in got[0].message


def test_collective_divergence_nested_and_else_branch():
    src = (
        "def step(x):\n"
        "    if jax.process_index() != 0:\n"
        "        y = 1\n"
        "    else:\n"
        "        for _ in range(3):\n"
        "            x = lax_ops.allgather(x)\n"
        "    return x\n")
    assert _codes(_run(ca.check_collective_divergence, src)) == ["JL101"]


def test_masked_contribution_idiom_is_clean():
    # the lax_ops.broadcast shape: EVERY worker calls the collective, the
    # rank condition only masks the contribution — no divergence
    src = (
        "def bcast(x, root):\n"
        "    mask = jax.lax.axis_index('workers') == root\n"
        "    return jax.lax.psum(jnp.where(mask, x, 0.0), 'workers')\n")
    assert _run(ca.check_collective_divergence, src) == []
    # rank-conditional HOST work (no collective inside) is also fine
    src2 = (
        "def save(x):\n"
        "    if jax.process_index() == 0:\n"
        "        np.savetxt('out.csv', x)\n")
    assert _run(ca.check_collective_divergence, src2) == []


# -- JL102 axis-name --------------------------------------------------------

def test_unknown_axis_literal_is_flagged():
    src = (
        "def step(x):\n"
        "    return jax.lax.psum(x, axis_name='worker')\n")   # typo'd axis
    got = _run(ca.check_axis_name, src)
    assert _codes(got) == ["JL102"] and "'worker'" in got[0].message


def test_declared_or_canonical_axes_are_clean():
    src = (
        "MY_AXIS = 'ring'\n"
        "def step(x, mesh):\n"
        "    a = jax.lax.psum(x, 'workers')\n"        # canonical
        "    b = jax.lax.all_gather(x, 'ring')\n"     # declared above
        "    c = lax_ops.allreduce(x, axis_name=WORKERS)\n"  # constant ref
        "    return a, b, c\n")
    assert _run(ca.check_axis_name, src) == []


# -- JL103 retrace-hazard ---------------------------------------------------

def test_immediately_invoked_jit_is_flagged():
    src = (
        "def fit(sess, x):\n"
        "    return sess.spmd(lambda a: a + 1, in_specs=s, out_specs=s)(x)\n")
    got = _run(ca.check_retrace_hazard, src)
    assert _codes(got) == ["JL103"] and "one expression" in got[0].message


def test_jit_in_loop_without_cache_guard_is_flagged():
    src = (
        "def fit(sess, xs):\n"
        "    for x in xs:\n"
        "        f = jax.jit(step)\n"
        "        f(x)\n")
    assert _codes(_run(ca.check_retrace_hazard, src)) == ["JL103"]
    # the repo's cache idiom is clean: the wrapper is STORED in a container
    guarded = (
        "def fit(self, sess, xs):\n"
        "    for x in xs:\n"
        "        if x.shape not in self._fns:\n"
        "            self._fns[x.shape] = jax.jit(step)\n"
        "        self._fns[x.shape](x)\n")
    assert _run(ca.check_retrace_hazard, guarded) == []
    # an unrelated `not in` membership test is NOT a cache: a plain-name
    # bind inside it still rebuilds the wrapper every iteration
    skip_filter = (
        "def fit(sess, xs):\n"
        "    for x in xs:\n"
        "        if x.tag not in SKIP:\n"
        "            f = jax.jit(step)\n"
        "            f(x)\n")
    assert _codes(_run(ca.check_retrace_hazard, skip_filter)) == ["JL103"]


def test_jitted_mutable_default_and_global_are_flagged():
    src = (
        "@jax.jit\n"
        "def step(x, opts={}):\n"
        "    return x\n")
    assert _codes(_run(ca.check_retrace_hazard, src)) == ["JL103"]
    src2 = (
        "@partial(jax.jit, static_argnums=(1,))\n"
        "def step(x, n):\n"
        "    global _SCALE\n"
        "    return x * _SCALE\n")
    assert _codes(_run(ca.check_retrace_hazard, src2)) == ["JL103"]
    # plain decorated function with hashable defaults is clean
    assert _run(ca.check_retrace_hazard,
                "@jax.jit\ndef step(x, n=3):\n    return x * n\n") == []


# -- JL104 host-sync-hot-loop ----------------------------------------------

def test_host_sync_inside_fit_loop_is_flagged():
    src = (
        "def fit(self, xs):\n"
        "    costs = []\n"
        "    for x in xs:\n"
        "        c = self._step(x)\n"
        "        costs.append(np.asarray(c).tolist())\n"
        "        c.block_until_ready()\n"
        "        n = c.item()\n"
        "    return costs\n")
    got = _run(ca.check_host_sync, src)
    assert _codes(got) == ["JL104"] * 3


def test_host_sync_outside_loop_or_fit_is_clean():
    # after the loop: one sync per fit is fine
    src = ("def fit(self, xs):\n"
           "    for x in xs:\n"
           "        c = self._step(x)\n"
           "    return np.asarray(c)\n")
    assert _run(ca.check_host_sync, src) == []
    # not a fit/train path: loaders may asarray per file
    src2 = ("def load(paths):\n"
            "    return [np.asarray(read(p)) for p in paths]\n")
    assert _run(ca.check_host_sync, src2) == []


# -- JL105 broad-except -----------------------------------------------------

def test_broad_except_variants_are_flagged():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except (ValueError, BaseException):\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        pass\n")
    assert _codes(_run(ca.check_broad_except, src)) == ["JL105"] * 3
    assert _run(ca.check_broad_except,
                "def f():\n"
                "    try:\n"
                "        import scipy\n"
                "    except ImportError:\n"
                "        scipy = None\n") == []


# -- JL106 scatter (folded lint_scatter) ------------------------------------

def test_scatter_in_hot_tree_flagged_and_cold_tree_exempt():
    src = "def hot(x, i, v):\n    return x.at[i].add(v)\n"
    assert _codes(_run(ca.check_scatter, src,
                       "harp_tpu/models/fake.py")) == ["JL106"]
    assert _codes(_run(ca.check_scatter, src,
                       "harp_tpu/ops/fake.py")) == ["JL106"]
    # gathers and non-hot trees don't trip
    assert _run(ca.check_scatter, "def f(x, i):\n    return x[i]\n",
                "harp_tpu/models/fake.py") == []
    assert _run(ca.check_scatter, src, "harp_tpu/parallel/fake.py") == []


# -- allowlist contract -----------------------------------------------------

def test_allowlist_suppresses_and_staleness_fails():
    f = Finding("JL105", "broad-except", "harp_tpu/models/fake.py", 3,
                "f", "msg")
    ok = {("harp_tpu/models/fake.py", "f", "JL105"):
          "a justification long enough to satisfy the schema"}
    active, stale = apply_allowlist([f], ok)
    assert active == [] and stale == []
    # same entry with no matching finding -> stale, loudly
    active, stale = apply_allowlist([], ok)
    assert active == [] and len(stale) == 1 and "prune" in stale[0]


def test_allowlist_requires_real_justifications():
    assert validate_allowlist(
        {("a.py", "f", "JL105"): "ok"}) != []            # too short
    assert validate_allowlist({("a.py", "f"): "x" * 40}) != []   # bad key
    assert validate_allowlist(
        {("a.py", "f", "JL105"): "cold prepare-side layout, runs once"}
    ) == []


def test_committed_allowlist_is_schema_valid_and_live():
    assert validate_allowlist(ALLOWLIST) == []
    raw = run_ast_checkers(REPO, ca.ast_checkers_for_repo(REPO))
    _active, stale = apply_allowlist(raw, ALLOWLIST)
    assert stale == [], "\n".join(stale)


# -- the repo itself lints clean (tier-1 wiring) ----------------------------

def test_repo_is_clean_under_all_ast_checkers():
    raw = run_ast_checkers(REPO, ca.ast_checkers_for_repo(REPO))
    active, _stale = apply_allowlist(raw, ALLOWLIST)
    assert active == [], "\n".join(str(f) for f in active)


# -- jaxpr engine: collective budget + dtype policy -------------------------

def test_traced_budgets_match_committed_manifest(session):
    # `session` fixture guarantees the 8-device mesh is up; trace_all then
    # reuses the already-initialized backend
    traced = checkers_jaxpr.trace_all()
    findings = checkers_jaxpr.check_budget(REPO, traced)
    assert findings == [], "\n".join(str(f) for f in findings)
    # the manifest's collective KINDS are the comm contract: the flagship
    # regroupallgather variant must stay reduce_scatter+all_gather (+ the
    # cost psum), not degrade to, e.g., a pair of psums
    counts, dtype_bad, nbytes = traced["kmeans_regroupallgather"]
    assert counts == {"psum": 1, "reduce_scatter": 1, "all_gather": 1}
    assert dtype_bad == []
    # the byte contract: every target carries per-kind operand bytes, and
    # the quantized twins sit well below their f32 programs — a quantized
    # path silently reverting to f32 moves these and fails JL203
    f32_bytes = sum(traced["kmeans_allreduce"][2].values())
    int8_bytes = sum(traced["kmeans_allreduce_int8"][2].values())
    assert 0 < int8_bytes < f32_bytes / 2, (int8_bytes, f32_bytes)
    assert sum(traced["sgd_mf_dense_int8"][2].values()) < sum(
        traced["sgd_mf_dense"][2].values())
    # the quantized SERVING wire (ISSUE 17): same route/route-back shape
    # (3 all_to_all + 1 psum), strictly fewer bytes than the f32 dispatch
    # — an endpoint silently reverting to f32 payloads fails JL203 here
    serve_counts, _, serve_f32 = traced["serve_topk_mf"]
    serve_counts_i8, _, serve_i8 = traced["serve_topk_mf_int8"]
    assert serve_counts_i8 == serve_counts
    assert 0 < sum(serve_i8.values()) < sum(serve_f32.values())
    assert sum(nbytes.values()) > 0


def test_budget_drift_and_stale_rows_are_loud():
    traced = {"kmeans_regroupallgather": ({"psum": 5}, [], {"psum": 20})}
    findings = checkers_jaxpr.check_budget(REPO, traced)
    msgs = "\n".join(f.message for f in findings)
    # count drift on the one traced target...
    assert any(f.code == "JL201" and "drift" in f.message
               and f.func == "kmeans_regroupallgather" for f in findings)
    assert "traced 5 vs pinned 1" in msgs
    # ...and every other committed row reports as stale/unmatched
    assert any("matches no trace target" in f.message for f in findings)


def test_byte_budget_drift_is_loud_at_same_counts():
    # JL203's reason to exist: SAME collective counts, different operand
    # bytes (the silently-dropped-quantization signature) must fail even
    # though JL201 sees no drift
    import json

    with open(os.path.join(REPO, checkers_jaxpr.BUDGET_FILE)) as f:
        manifest = json.load(f)
    row = manifest["targets"]["kmeans_allreduce"]
    counts = dict(row["collectives"])
    widened = {k: 4 * v for k, v in row["bytes_by_kind"].items()}
    traced = {"kmeans_allreduce": (counts, [], widened)}
    findings = checkers_jaxpr.check_budget(REPO, traced)
    assert not any(f.code == "JL201" and f.func == "kmeans_allreduce"
                   for f in findings)
    hits = [f for f in findings
            if f.code == "JL203" and f.func == "kmeans_allreduce"]
    assert hits and "byte-budget drift" in hits[0].message
    # a manifest row lacking bytes_per_step is itself a finding
    clean = {"kmeans_allreduce": (counts, [],
                                  dict(row["bytes_by_kind"]))}
    assert not any(f.func == "kmeans_allreduce"
                   for f in checkers_jaxpr.check_budget(REPO, clean))


def test_dtype_policy_reports_bf16_accumulation():
    import jax
    import jax.numpy as jnp

    def bad(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

    x = jnp.zeros((4, 4), jnp.bfloat16)
    closed = jax.make_jaxpr(bad)(x, x)
    counts, dtype_bad = {}, []
    checkers_jaxpr._walk(closed.jaxpr, counts, dtype_bad, {})
    assert any("bf16" in m for m in dtype_bad)

    def good(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    counts, dtype_bad = {}, []
    checkers_jaxpr._walk(jax.make_jaxpr(good)(x, x).jaxpr, counts, dtype_bad,
                         {})
    assert dtype_bad == []


# -- JL3xx concurrency engine (ISSUE 13 tentpole) ---------------------------

from tools.jaxlint.checkers_threads import check_concurrency  # noqa: E402

_HOST_REL = "harp_tpu/serve/fake.py"


def _runc(src, rel=_HOST_REL):
    return check_concurrency(ast.parse(src), rel, src)


def test_jl301_doctored_unguarded_shared_write_fails_loudly():
    # the acceptance fixture: a receive-loop thread writes state the main
    # thread reads, no lock anywhere — the PR 10-12 hand-review bug class
    src = (
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def _loop(self):\n"
        "        self.state = 'running'\n"
        "    def poke(self):\n"
        "        return self.state\n")
    got = _runc(src)
    assert _codes(got) == ["JL301"]
    assert got[0].func == "_loop" and "self.state" in got[0].message
    assert "thread:_loop" in got[0].message


def test_jl301_guarded_write_twin_is_clean():
    src = (
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def _loop(self):\n"
        "        with self._lock:\n"
        "            self.state = 'running'\n"
        "    def poke(self):\n"
        "        with self._lock:\n"
        "            return self.state\n")
    assert _runc(src) == []
    # an Event signal instead of a bare flag is also clean (sync
    # primitives manage their own safety)
    src2 = (
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._draining = threading.Event()\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def _loop(self):\n"
        "        if self._draining.is_set():\n"
        "            return\n"
        "    def begin_drain(self):\n"
        "        self._draining.set()\n")
    assert _runc(src2) == []


def test_jl301_only_fires_in_host_trees():
    src = (
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def _loop(self):\n"
        "        self.state = 1\n"
        "    def poke(self):\n"
        "        return self.state\n")
    assert _runc(src, "harp_tpu/models/fake.py") == []
    assert _codes(_runc(src, "harp_tpu/telemetry/fake.py")) == ["JL301"]


def test_jl302_unsynchronized_rmw_and_check_then_act_are_flagged():
    src = (
        "class Counter:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "        self._d = {}\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def _loop(self):\n"
        "        self._n += 1\n"
        "        if 'k' in self._d:\n"
        "            self._d.pop('k')\n"
        "    def read(self):\n"
        "        return self._n, self._d.get('k')\n")
    got = _runc(src)
    assert _codes(got) == ["JL302", "JL302"]
    assert "read-modify-write" in got[0].message
    assert "check-then-act" in got[1].message


def test_jl302_guarded_rmw_twin_is_clean():
    src = (
        "class Counter:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "        self._d = {}\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def _loop(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "            if 'k' in self._d:\n"
        "                self._d.pop('k')\n"
        "    def read(self):\n"
        "        with self._lock:\n"
        "            return self._n, self._d.get('k')\n")
    assert _runc(src) == []


def test_jl303_doctored_lock_order_inversion_fails_loudly():
    src = (
        "class AB:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n")
    got = _runc(src)
    assert _codes(got) == ["JL303"]
    assert "deadlock" in got[0].message.lower()
    assert "_a" in got[0].message and "_b" in got[0].message


def test_jl303_cross_method_inversion_via_call_under_lock():
    # one() holds _a and CALLS a method that takes _b; two() nests b -> a
    src = (
        "class AB:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            self.take_b()\n"
        "    def take_b(self):\n"
        "        with self._b:\n"
        "            pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n")
    assert _codes(_runc(src)) == ["JL303"]


def test_jl303_consistent_order_twin_is_clean():
    src = (
        "class AB:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n")
    assert _runc(src) == []


def test_jl304_unjoined_non_daemon_thread_is_flagged():
    src = (
        "class Spawner:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "        self._t.start()\n"
        "    def _loop(self):\n"
        "        pass\n")
    got = _runc(src)
    assert _codes(got) == ["JL304"] and "self._t" in got[0].message
    # module-level function variant
    src2 = (
        "def fire_and_forget(fn):\n"
        "    t = threading.Thread(target=fn)\n"
        "    t.start()\n")
    assert _codes(_runc(src2)) == ["JL304"]


def test_jl304_joined_or_daemon_twins_are_clean():
    joined = (
        "class Spawner:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "        self._t.start()\n"
        "    def _loop(self):\n"
        "        pass\n"
        "    def close(self):\n"
        "        self._t.join(5.0)\n")
    assert _runc(joined) == []
    daemon = (
        "class Spawner:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._loop, daemon=True)\n"
        "        self._t.start()\n"
        "    def _loop(self):\n"
        "        pass\n")
    assert _runc(daemon) == []
    # local thread joined in the same function
    local = (
        "def run_and_wait(fn):\n"
        "    t = threading.Thread(target=fn)\n"
        "    t.start()\n"
        "    t.join()\n")
    assert _runc(local) == []


def test_jl3xx_callback_protocol_flags_hook_state():
    # __call__ is the hook/callback protocol: registered by one thread,
    # invoked by another — public attrs written there are the class's
    # cross-thread read surface (the GangCollector/exporter race)
    src = (
        "class Hook:\n"
        "    def __call__(self, i, log):\n"
        "        self.last = i\n")
    got = _runc(src, "harp_tpu/telemetry/fake.py")
    assert _codes(got) == ["JL301"]
    # a lock-guarded publish is the clean twin
    src2 = (
        "class Hook:\n"
        "    def __init__(self):\n"
        "        self._publish_lock = threading.Lock()\n"
        "    def __call__(self, i, log):\n"
        "        with self._publish_lock:\n"
        "            self._last = i\n"
        "    @property\n"
        "    def last(self):\n"
        "        with self._publish_lock:\n"
        "            return self._last\n")
    assert _runc(src2, "harp_tpu/telemetry/fake.py") == []


def test_jl3xx_rides_the_allowlist_and_staleness_contract():
    # suppression and the staleness guarantee extend to JL3xx unchanged
    f = Finding("JL301", "unguarded-shared-write", _HOST_REL, 7, "_loop",
                "msg")
    ok = {(_HOST_REL, "_loop", "JL301"):
          "sticky single-writer flag, GIL-atomic store, reader tolerates "
          "one-interval staleness"}
    active, stale = apply_allowlist([f], ok)
    assert active == [] and stale == []
    active, stale = apply_allowlist([], ok)
    assert active == [] and len(stale) == 1 and "prune" in stale[0]


def test_repo_host_plane_is_clean_under_concurrency_checker():
    # the tentpole's acceptance: the checker runs clean on the repo, with
    # every pre-existing real finding fixed or individually justified
    raw = run_ast_checkers(REPO, [check_concurrency])
    active, _stale = apply_allowlist(raw, ALLOWLIST)
    assert active == [], "\n".join(str(f) for f in active)
    # ... and the justified exemptions are LIVE findings, not blanket
    # passes: the raw run still sees the allowlisted sites
    raw_keys = {f.key for f in raw}
    for key in [k for k in ALLOWLIST if k[2].startswith("JL30")]:
        assert key in raw_keys, f"stale JL3xx allowlist entry {key}"


# -- gang-mode collective budgets (ISSUE 13 tentpole, part 2) ---------------

import copy  # noqa: E402
import json  # noqa: E402


def _gang_manifest_rows():
    with open(os.path.join(REPO, checkers_jaxpr.BUDGET_FILE)) as f:
        return json.load(f)["gang_targets"]


def _as_traced(rows):
    return {name: dict(row, _dtype_bad=[]) for name, row in rows.items()}


def test_gang_manifest_pins_three_plus_targets_with_link_split():
    rows = _gang_manifest_rows()
    assert len(rows) >= 3, sorted(rows)
    for name, row in rows.items():
        assert row["processes"] >= 2, name
        assert row["processes"] * row["devices_per_process"] == 8, name
        assert row["per_process_shard_shapes"], name
        # the link split partitions bytes_by_kind exactly, per kind
        for kind, b in row["bytes_by_kind"].items():
            dcn = row["bytes_by_link"]["dcn"][kind]
            ici = row["bytes_by_link"]["ici"][kind]
            assert dcn + ici == b, (name, kind)
            assert dcn > 0, (name, kind)   # a 2-process gang always
            #                                crosses the DCN
        assert row["dcn_bytes_per_step"] == sum(
            row["bytes_by_link"]["dcn"].values()), name
    # manifest rows self-check clean against themselves
    assert checkers_jaxpr.check_gang_budget(REPO, _as_traced(rows)) == []


def test_gang_doctored_dcn_byte_count_fails_jl203():
    # the acceptance criterion: doctoring a DCN byte count fails JL203
    rows = _as_traced(_gang_manifest_rows())
    name = sorted(rows)[0]
    row = copy.deepcopy(rows[name])
    kind = sorted(row["bytes_by_link"]["dcn"])[0]
    row["bytes_by_link"]["dcn"][kind] += 4096
    row["dcn_bytes_per_step"] += 4096
    doctored = dict(rows, **{name: row})
    findings = checkers_jaxpr.check_gang_budget(REPO, doctored)
    hits = [f for f in findings if f.code == "JL203" and f.func == name]
    assert hits and "DCN" in hits[0].message, findings
    assert not any(f.code == "JL201" and f.func == name for f in findings)


def test_gang_doctored_shard_shape_fails_jl201():
    rows = _as_traced(_gang_manifest_rows())
    name = sorted(rows)[0]
    row = copy.deepcopy(rows[name])
    row["per_process_shard_shapes"][0][0] *= 2
    findings = checkers_jaxpr.check_gang_budget(
        REPO, dict(rows, **{name: row}))
    hits = [f for f in findings if f.code == "JL201" and f.func == name]
    assert hits and "shard shapes" in hits[0].message, findings


def test_gang_missing_and_stale_rows_are_loud():
    rows = _as_traced(_gang_manifest_rows())
    # a gang target with no manifest row
    extra = dict(rows)
    extra["gang2x4_new_workload"] = copy.deepcopy(
        rows[sorted(rows)[0]])
    findings = checkers_jaxpr.check_gang_budget(REPO, extra)
    assert any(f.code == "JL201" and "no manifest row" in f.message
               for f in findings)
    # a manifest row whose target vanished
    short = dict(rows)
    dropped = sorted(short)[0]
    del short[dropped]
    findings = checkers_jaxpr.check_gang_budget(REPO, short)
    assert any(f.code == "JL201" and f.func == dropped
               and "stale" in f.message for f in findings)


def test_split_bytes_by_link_edge_model():
    split = checkers_jaxpr.split_bytes_by_link
    # ring kinds: P of W edges cross the DCN -> 2/8 here
    out = split({"ppermute": 800}, world=8, processes=2,
                devices_per_process=4, link_class="dcn")
    assert out["dcn"]["ppermute"] == 200
    assert out["ici"]["ppermute"] == 600
    # all_to_all: W-D of W-1 peers are remote -> 4/7
    out = split({"all_to_all": 700}, world=8, processes=2,
                devices_per_process=4, link_class="dcn")
    assert out["dcn"]["all_to_all"] == 400
    assert out["ici"]["all_to_all"] == 300
    # floor split still sums exactly on odd byte counts
    out = split({"ppermute": 101}, world=8, processes=2,
                devices_per_process=4, link_class="dcn")
    assert out["dcn"]["ppermute"] + out["ici"]["ppermute"] == 101
    # a single-pod gang (workers axis hinted ici) books everything as ICI
    out = split({"ppermute": 800}, world=8, processes=2,
                devices_per_process=4, link_class="ici")
    assert out["dcn"]["ppermute"] == 0 and out["ici"]["ppermute"] == 800


def test_gang_traced_budgets_match_committed_manifest(session):
    # the end-to-end gate: retracing the gang registry on the live mesh
    # reproduces the committed rows exactly (any drift is loud)
    gang = checkers_jaxpr.trace_gang_all()
    findings = checkers_jaxpr.check_gang_budget(REPO, gang)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert len(gang) >= 3
    for name, row in gang.items():
        assert row["_dtype_bad"] == [], name


# -- --json machine-readable output (ISSUE 13 satellite) --------------------


def test_json_output_one_finding_per_line(tmp_path, capsys):
    pkg = tmp_path / "harp_tpu" / "serve"
    pkg.mkdir(parents=True)
    (pkg / "racy.py").write_text(
        "import threading\n"
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def _loop(self):\n"
        "        self.state = 1\n"
        "    def poke(self):\n"
        "        return self.state\n")
    from tools.jaxlint.__main__ import main as jaxlint_main

    rc = jaxlint_main([str(tmp_path), "--ast-only", "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    lines = [json.loads(line) for line in out.strip().splitlines()]
    assert lines, out
    for rec in lines:
        assert {"file", "line", "code", "checker", "func", "message",
                "allowlisted"} <= set(rec), rec
    jl301 = [r for r in lines if r["code"] == "JL301"]
    assert jl301 and jl301[0]["file"] == "harp_tpu/serve/racy.py"
    assert jl301[0]["line"] == 7 and jl301[0]["func"] == "_loop"
    assert jl301[0]["allowlisted"] is False
    # human-mode summary lines must NOT pollute the JSONL stream
    assert not any(line.startswith(("ast engine", "jaxlint"))
                   for line in out.strip().splitlines())


def test_json_stale_allowlist_records_ride_the_jsonl_stream(tmp_path,
                                                            capsys):
    (tmp_path / "harp_tpu").mkdir()
    (tmp_path / "harp_tpu" / "clean.py").write_text("X = 1\n")
    from tools.jaxlint.__main__ import main as jaxlint_main

    rc = jaxlint_main([str(tmp_path), "--ast-only", "--json"])
    out = capsys.readouterr().out
    # the fixture tree itself is clean, but the committed allowlist is
    # stale against it — staleness must surface as machine-readable
    # records on the same stream (and keep the nonzero exit), never as
    # human prose polluting the JSONL
    lines = [json.loads(line) for line in out.strip().splitlines()]
    assert lines and all(rec["code"] == "stale-allowlist" for rec in lines)
    assert rc == 1  # stale entries are findings by contract


def test_json_deferred_callback_write_is_not_guard_shadowed():
    # a closure DEFINED under a lock executes later without it: its
    # unguarded write must still fire (the guard state does not leak in)
    src = (
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._thread = threading.Thread(target=self._loop,\n"
        "                                        daemon=True)\n"
        "    def make_cb(self):\n"
        "        with self._lock:\n"
        "            def cb():\n"
        "                self.state = 1\n"
        "            self._cb = cb\n"
        "    def _loop(self):\n"
        "        self.state = 2\n"
        "    def poke(self):\n"
        "        return self.state\n")
    got = _runc(src)
    assert sorted((f.func, f.code) for f in got) == [
        ("_loop", "JL301"), ("make_cb", "JL301")], got


def test_jl301_nested_fn_thread_target_makes_method_a_root():
    # a Thread targeting a function NESTED in a method: the closure's
    # unguarded cross-thread write must fire (the enclosing method hosts
    # the thread domain)
    src = (
        "class Worker:\n"
        "    def start(self):\n"
        "        def loop():\n"
        "            self.state = 1\n"
        "        threading.Thread(target=loop, daemon=True).start()\n"
        "    def poke(self):\n"
        "        return self.state\n")
    got = _runc(src)
    assert [(f.func, f.code) for f in got] == [("start", "JL301")], got


# -- JL4xx static memory engine (ISSUE 19) ----------------------------------

import numpy as np  # noqa: E402

from harp_tpu.aot import static_memory  # noqa: E402
from tools.jaxlint import checkers_memory  # noqa: E402


def _memory_manifest_rows():
    with open(os.path.join(REPO, checkers_memory.BUDGET_FILE)) as f:
        return json.load(f)["memory"]


def test_memory_manifest_pins_twelve_plus_targets():
    rows = _memory_manifest_rows()
    assert len(rows) >= 12, sorted(rows)
    for name, row in rows.items():
        assert set(checkers_memory.MEMORY_FIELDS) <= set(row), name
        assert row["resident_arg_bytes"] > 0, name
        assert row["peak_live_bytes"] >= row["resident_arg_bytes"], name
        assert row["transient_peak_ratio"] == round(
            row["peak_live_bytes"] / row["resident_arg_bytes"],
            static_memory.RATIO_DIGITS), name
        # every committed program sits under the JL404 absolute guard
        assert (row["transient_peak_ratio"]
                < checkers_memory.TRANSIENT_BLOWUP_RATIO), name
    # both serving dispatches are pinned, and the int8 resident footprint
    # sits strictly below the f32 twin's — the quantized mode's memory
    # story, now a static number the mall can plan on
    assert (rows["serve_topk_mf_int8"]["resident_arg_bytes"]
            < rows["serve_topk_mf"]["resident_arg_bytes"])
    assert "serve_classify_nn" in rows
    assert any(name.startswith("gang2x4_") for name in rows), sorted(rows)
    # manifest rows self-check clean against themselves
    assert checkers_memory.check_memory_budget(REPO, dict(rows)) == []


def test_memory_doctored_peak_row_fails_jl401():
    # the acceptance criterion: doctoring a peak_live_bytes row fails
    # JL401 loudly, and ONLY for the doctored target
    rows = _memory_manifest_rows()
    doctored = copy.deepcopy(rows)
    doctored["serve_topk_mf"]["peak_live_bytes"] += 4096
    findings = checkers_memory.check_memory_budget(REPO, doctored)
    hits = [f for f in findings
            if f.code == "JL401" and f.func == "serve_topk_mf"]
    assert hits and "drift" in hits[0].message, findings
    assert "peak_live_bytes" in hits[0].message
    assert all(f.func == "serve_topk_mf" for f in findings), findings


def test_memory_missing_stale_and_absent_section_are_loud(tmp_path):
    rows = _memory_manifest_rows()
    # a traced target with no manifest row
    extra = copy.deepcopy(rows)
    extra["serve_new_workload"] = dict(extra[sorted(extra)[0]])
    findings = checkers_memory.check_memory_budget(REPO, extra)
    assert any(f.code == "JL401" and "no memory row" in f.message
               for f in findings)
    # a manifest row whose target vanished
    short = copy.deepcopy(rows)
    dropped = sorted(short)[0]
    del short[dropped]
    findings = checkers_memory.check_memory_budget(REPO, short)
    assert any(f.code == "JL401" and f.func == dropped
               and "stale" in f.message for f in findings)
    # a manifest missing the whole memory section (pre-r20 checkout)
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "collective_budget.json").write_text(
        json.dumps({"targets": {}}))
    findings = checkers_memory.check_memory_budget(str(tmp_path), rows)
    assert [f.code for f in findings] == ["JL401"], findings
    assert "no memory section" in findings[0].message


def test_jl402_dropped_donation_fixture_and_honored_twin(session):
    import jax

    x = np.ones(8, np.float32)
    # f32 input donated, scalar output: no output aval matches, XLA
    # drops the donation silently — JL402's reason to exist
    dropped = jax.make_jaxpr(
        lambda v: jax.jit(lambda y: y.sum(), donate_argnums=(0,))(v))(x)
    findings = checkers_memory.donation_findings(dropped, "fixture")
    assert [f.code for f in findings] == ["JL402"], findings
    assert "aliases NO output" in findings[0].message
    assert findings[0].func == "fixture"
    # the clean twin: same donation, but the output aval matches — the
    # donation is honored, nothing fires
    honored = jax.make_jaxpr(
        lambda v: jax.jit(lambda y: y + 1, donate_argnums=(0,))(v))(x)
    assert checkers_memory.donation_findings(honored, "fixture") == []


def test_jl403_constant_bloat_fixture_and_small_const_twin(session):
    import jax

    big = np.ones((128, 128), np.float32)      # 64 KiB: at the threshold
    bloated = jax.make_jaxpr(lambda v: v[:128, :128] + big)(
        np.ones((256, 256), np.float32))
    findings = checkers_memory.const_findings(bloated, "fixture")
    assert [f.code for f in findings] == ["JL403"], findings
    assert "65536 B" in findings[0].message
    # the clean twin: a tiny closed-over constant rides below threshold
    small = np.ones((4,), np.float32)
    lean = jax.make_jaxpr(lambda v: v + small)(np.ones(4, np.float32))
    assert checkers_memory.const_findings(lean, "fixture") == []


def test_jl404_broadcast_blowup_fixture_and_calm_twin(session):
    import jax
    import jax.numpy as jnp

    x = np.ones(8, np.float32)
    # 32 B of arguments materializing a 128 KiB broadcast: the static
    # signature of an accidental full gather/broadcast
    blown = jax.make_jaxpr(
        lambda v: jnp.broadcast_to(v, (4096, 8)).sum())(x)
    findings = checkers_memory.transient_findings(blown, "fixture")
    assert [f.code for f in findings] == ["JL404"], findings
    assert "4097.0x" in findings[0].message
    calm = jax.make_jaxpr(lambda v: v * 2.0)(x)
    assert checkers_memory.transient_findings(calm, "fixture") == []


def test_memory_traced_rows_match_committed_manifest(session):
    # the end-to-end gate: re-analyzing every traced program reproduces
    # the committed memory rows exactly, and the repo's own programs
    # carry no JL402/403/404 hazards (every donation aliases, no captured
    # constants above threshold, no transient blowup)
    mem = checkers_memory.trace_memory_all()
    findings = checkers_memory.check_memory_budget(REPO, mem)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert len(mem) >= 12
    assert checkers_memory.check_memory_hazards() == []


def test_static_resident_bytes_cross_checks_endpoint_gauge(session):
    # the mall-planning contract: the static resident estimate equals the
    # endpoint's runtime resident-state gauge plus the placed query
    # buffer (the only dispatch argument that is not resident state) —
    # for BOTH endpoint families, and both match the committed rows
    # (the manifest is traced at these exact tier-1 shapes)
    import jax

    from harp_tpu.models import nn
    from harp_tpu.serve import endpoints as serve_ep

    rows = _memory_manifest_rows()
    rng = np.random.default_rng(0)
    uf = rng.normal(size=(64, 8)).astype(np.float32)
    items = rng.normal(size=(32, 8)).astype(np.float32)
    ep = serve_ep.TopKEndpoint(session, "mf", uf, items, k=4)
    ids = rng.integers(0, 64, size=ep.bucket_sizes[0])
    fn, args, _n, _bucket = ep.prepared(ids)
    row = static_memory.memory_row(jax.make_jaxpr(fn)(*args))
    assert row["resident_arg_bytes"] == (
        ep.resident_bytes() + int(args[-1].nbytes))
    assert row == rows["serve_topk_mf"]

    model = nn.MLPClassifier(session, nn.NNConfig(layers=(8,),
                                                  num_classes=3))
    model.params = nn.init_params((12, 8, 3), seed=0)
    cep = serve_ep.classify_from_nn(session, model, name="nn")
    x = rng.normal(size=(cep.bucket_sizes[0], 12)).astype(np.float32)
    cfn, cargs, _cn, _cbucket = cep.prepared(x)
    crow = static_memory.memory_row(jax.make_jaxpr(cfn)(*cargs))
    assert crow["resident_arg_bytes"] == (
        cep.resident_bytes() + int(cargs[-1].nbytes))
    assert crow == rows["serve_classify_nn"]


def test_memory_only_flag_runs_exactly_one_engine(session, capsys):
    from tools.jaxlint.__main__ import main as jaxlint_main

    rc = jaxlint_main(["--memory-only"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "memory engine:" in out
    for banner in ("ast engine", "jaxpr engine", "gang engine",
                   "artifact engine"):
        assert banner not in out, out


def test_memory_doctored_manifest_fails_jl401_in_json_stream(
        session, tmp_path, capsys):
    # end to end through the CLI: a doctored peak in a copied manifest
    # surfaces as a machine-readable JL401 record on the JSONL stream
    # with the full record schema, and the exit goes nonzero
    (tmp_path / "tools").mkdir()
    with open(os.path.join(REPO, checkers_memory.BUDGET_FILE)) as f:
        doc = json.load(f)
    doc["memory"]["serve_topk_mf"]["peak_live_bytes"] += 4096
    (tmp_path / "tools" / "collective_budget.json").write_text(
        json.dumps(doc))
    from tools.jaxlint.__main__ import main as jaxlint_main

    rc = jaxlint_main([str(tmp_path), "--memory-only", "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    lines = [json.loads(line) for line in out.strip().splitlines()]
    hits = [r for r in lines if r["code"] == "JL401"]
    assert hits and hits[0]["func"] == "serve_topk_mf", out
    assert hits[0]["allowlisted"] is False
    assert "drift" in hits[0]["message"]
    assert {"file", "line", "code", "checker", "func", "message",
            "allowlisted"} <= set(hits[0])


# -- JL5xx lowered-HLO engine (ISSUE 20) -------------------------------------

import pytest  # noqa: E402

from harp_tpu.aot import hlo_audit  # noqa: E402
from tools.jaxlint import checkers_hlo  # noqa: E402
from tools.jaxlint.core import split_allowlist  # noqa: E402


def _hlo_section():
    with open(os.path.join(REPO, checkers_hlo.BUDGET_FILE)) as f:
        return json.load(f)["hlo"]


def _write_budget(tmp_path, doc):
    (tmp_path / "tools").mkdir(exist_ok=True)
    (tmp_path / "tools" / "collective_budget.json").write_text(
        json.dumps(doc))


# a minimal post-SPMD module in the shapes the parser consumes: a tuple-
# result async all-reduce pair (books ONCE, at the -start), a while loop,
# and per-device entry parameters
_HLO_FIXTURE = """\
HloModule fixture_spmd

%body (p: (s32[], f32[8,2])) -> (s32[], f32[8,2]) {
  %p = (s32[], f32[8,2]{1,0}) parameter(0)
  %ars = (f32[8,2]{1,0}, f32[8,2]{1,0}) all-reduce-start(f32[8,2]{1,0} %x), to_apply=%add
  %ard = (f32[8,2]{1,0}, f32[8,2]{1,0}) all-reduce-done((f32[8,2]{1,0}, f32[8,2]{1,0}) %ars)
  ROOT %t = (s32[], f32[8,2]{1,0}) tuple(s32[] %i, f32[8,2]{1,0} %y)
}

ENTRY %main.9_spmd (param.1: f32[8,2], param.0: s32[]) -> (s32[], f32[8,2]) {
  %param.0 = s32[] parameter(1)
  %param.1 = f32[8,2]{1,0} parameter(0)
  %init = (s32[], f32[8,2]{1,0}) tuple(s32[] %param.0, f32[8,2]{1,0} %param.1)
  ROOT %w = (s32[], f32[8,2]{1,0}) while((s32[], f32[8,2]{1,0}) %init), condition=%cond, body=%body
}
"""


def test_hlo_parser_shapes_collectives_and_while():
    shapes = hlo_audit.parse_shapes("(f32[8,2]{1,0}, s32[], token[])")
    assert [str(s) for s in shapes] == ["f32[8,2]", "s32[]"]
    assert hlo_audit.shape_bytes("(f32[8,2]{1,0}, s32[])") == 64 + 4
    assert hlo_audit.shape_bytes("bf16[4,4]") == 32
    stats = hlo_audit.collective_stats(_HLO_FIXTURE)
    # the -start books the op once; the -done is the same transfer
    assert stats == {"all-reduce": {"count": 1, "bytes": 128,
                                    "shapes": ["f32[8,2]+f32[8,2]"]}}
    assert hlo_audit.while_count(_HLO_FIXTURE) == 1
    row = hlo_audit.hlo_row(_HLO_FIXTURE)
    assert row["collectives"] == {"all-reduce": 1}
    assert row["collective_bytes_total"] == 128
    assert row["while_count"] == 1
    assert row["instruction_count"] == 7
    # entry params surface per-DEVICE blocks, not argument order
    assert sorted(str(s) for s in
                  hlo_audit.entry_param_shapes(_HLO_FIXTURE)) == \
        ["f32[8,2]", "s32[]"]
    # the TPU compiler's tiled layouts carry parentheses (recorded from the
    # 4-chip NN step, PR 21): they must not end a tuple type early
    tpu = ("  %all-reduce.2 = (f32[128,256]{1,0:T(8,128)S(1)}, "
           "f32[256]{0:T(256)S(1)}, /*index=2*/f32[]{:T(128)}) "
           "all-reduce(%fusion.108, %gte.928, %f.3), channel_id=1\n"
           "  %while.99 = (s32[]{:T(128)}, f32[16]{0:T(128)S(1)}) "
           "while(%tuple.1), condition=%c, body=%b\n")
    assert hlo_audit.collective_stats(tpu) == {"all-reduce": {
        "count": 1, "bytes": (128 * 256 + 256 + 1) * 4,
        "shapes": ["f32[128,256]+f32[256]+f32[]"]}}
    assert hlo_audit.while_count(tpu) == 1


def test_jl501_injected_compiler_allgather_and_clean_twin():
    # the acceptance fixture: a compiler-side all-gather injected into a
    # module whose trace only showed a psum fails JL501 loudly, naming
    # the op, shape, and inferred cause
    doctored = (
        "HloModule fixture_spmd\n\n"
        "ENTRY %main.1_spmd (param.0: f32[8,16]) -> f32[64,16] {\n"
        "  %param.0 = f32[8,16]{1,0} parameter(0)\n"
        "  %ar = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %param.0)\n"
        "  ROOT %ag = f32[64,16]{1,0} all-gather(f32[8,16]{1,0} %ar), "
        "dimensions={0}\n"
        "}\n")
    findings = checkers_hlo.inserted_findings_from(
        doctored, {"psum": 1}, "fixture")
    assert [f.code for f in findings] == ["JL501"], findings
    msg = findings[0].message
    assert "all-gather" in msg and "f32[64,16]" in msg
    assert "full-broadcast" in msg          # the inferred cause family
    assert findings[0].func == "fixture"
    # clean twin 1: the SAME module when the trace owned the gather
    assert checkers_hlo.inserted_findings_from(
        doctored, {"psum": 1, "all_gather": 1}, "fixture") == []
    # clean twin 2: drop the injected op — a psum-only module is clean
    clean = doctored.replace(
        "  ROOT %ag = f32[64,16]{1,0} all-gather(f32[8,16]{1,0} %ar), "
        "dimensions={0}\n", "")
    assert checkers_hlo.inserted_findings_from(
        clean, {"psum": 1}, "fixture") == []


class _FakeSharded:
    """A placed-array stand-in: shape/dtype/sharding is all the audit
    reads off a leaf."""

    class _S:
        def __init__(self, shard):
            self._shard = shard

        def shard_shape(self, _global_shape):
            return self._shard

    def __init__(self, shape, shard):
        self.shape = shape
        self.dtype = np.dtype("float32")
        self.sharding = self._S(shard)


def test_jl503_replicated_where_sharded_and_clean_twin():
    args = (_FakeSharded((64, 16), (8, 16)),)
    # doctored: the partitioner compiled the declared-sharded operand at
    # its GLOBAL shape — the silent full-replication signature
    doctored = (
        "ENTRY %main.1_spmd (param.0: f32[64,16]) -> f32[64,16] {\n"
        "  %param.0 = f32[64,16]{1,0} parameter(0)\n"
        "}\n")
    findings = checkers_hlo.replicated_findings_from(doctored, args, "fx")
    assert [f.code for f in findings] == ["JL503"], findings
    assert "REPLICATED" in findings[0].message
    assert "f32[64,16]" in findings[0].message
    assert "f32[8,16]" in findings[0].message        # the declared block
    # clean twin: compiled at the declared per-device block
    clean = doctored.replace("f32[64,16]", "f32[8,16]")
    assert checkers_hlo.replicated_findings_from(clean, args, "fx") == []
    # conservative twin: a const-folded (dropped) param is NOT a finding
    folded = "ENTRY %main.1_spmd () -> f32[] {\n}\n"
    assert checkers_hlo.replicated_findings_from(folded, args, "fx") == []


def test_hlo_manifest_pins_all_targets_and_dispatch_matrix():
    from tools.jaxlint import trace_targets

    section = _hlo_section()
    rows = section["targets"]
    expected = set(trace_targets.TARGETS) | set(trace_targets.GANG_TARGETS)
    assert set(rows) == expected, sorted(expected ^ set(rows))
    for name, row in rows.items():
        assert set(checkers_hlo.HLO_FIELDS) <= set(row), name
        assert row["instruction_count"] > 0, name
        assert set(row["collectives"]) == set(row["collective_bytes"]), name
        assert row["collective_bytes_total"] == sum(
            row["collective_bytes"].values()), name
        assert set(row["collectives"]) <= set(
            hlo_audit.HLO_COLLECTIVE_OPS), name
    # the quantized serving dispatch moves FEWER compiled collective
    # bytes than its f32 twin at the same op count — the int8 wire story,
    # now a compiled-layer number
    assert (rows["serve_topk_mf_int8"]["collectives"]
            == rows["serve_topk_mf"]["collectives"])
    assert (rows["serve_topk_mf_int8"]["collective_bytes_total"]
            < rows["serve_topk_mf"]["collective_bytes_total"])
    # the device-kind matrix: cpu is always pinned, with all 6 serving
    # dispatches; mf routes stay collective, nn dispatches stay local
    matrix = section["device_kinds"]["cpu"]
    assert set(matrix) == {f"serve/{m}/b{b}" for m in ("mf", "nn")
                           for b in (8, 32, 128)}
    for name, row in matrix.items():
        if name.startswith("serve/mf/"):
            assert row["collectives"].get("all-to-all", 0) >= 1, name
        else:
            assert row["collectives"] == {}, name
    # the committed section self-checks clean
    assert checkers_hlo.check_hlo_budget(REPO, dict(rows),
                                         dict(matrix)) == []


def test_jl502_doctored_missing_stale_and_env_rows_are_loud(tmp_path):
    with open(os.path.join(REPO, checkers_hlo.BUDGET_FILE)) as f:
        doc = json.load(f)
    rows = doc["hlo"]["targets"]
    matrix = doc["hlo"]["device_kinds"]["cpu"]

    # the acceptance criterion: a doctored compiled row fails JL502
    # loudly, and ONLY for the doctored target
    doctored = copy.deepcopy(doc)
    doctored["hlo"]["targets"]["kmeans_allreduce"][
        "instruction_count"] += 7
    _write_budget(tmp_path, doctored)
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), dict(rows),
                                             dict(matrix))
    assert [(f.code, f.func) for f in findings] == \
        [("JL502", "kmeans_allreduce")], findings
    assert "drift" in findings[0].message
    assert "instruction_count" in findings[0].message

    # a lowered target with no pinned row / a row whose target vanished
    extra = dict(rows)
    extra["new_workload"] = dict(rows["kmeans_allreduce"])
    _write_budget(tmp_path, doc)
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), extra,
                                             dict(matrix))
    assert any(f.code == "JL502" and "no hlo row" in f.message
               for f in findings)
    short = dict(rows)
    del short["kmeans_allreduce"]
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), short,
                                             dict(matrix))
    assert any(f.code == "JL502" and f.func == "kmeans_allreduce"
               and "stale" in f.message for f in findings)

    # a manifest missing the whole hlo section (pre-r21 checkout)
    _write_budget(tmp_path, {"targets": {}})
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), dict(rows),
                                             dict(matrix))
    assert [f.code for f in findings] == ["JL502"], findings
    assert "no hlo section" in findings[0].message

    # a different jax version re-pins with ONE finding, not N drifts
    repinned = copy.deepcopy(doc)
    repinned["hlo"]["lowered_with_jax"] = "0.0.1"
    repinned["hlo"]["targets"]["kmeans_allreduce"][
        "instruction_count"] += 7
    _write_budget(tmp_path, repinned)
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), dict(rows),
                                             dict(matrix))
    assert len(findings) == 1 and "re-pin" in findings[0].message, findings


def test_jl504_doctored_device_kind_rows_are_loud(tmp_path):
    with open(os.path.join(REPO, checkers_hlo.BUDGET_FILE)) as f:
        doc = json.load(f)
    rows = doc["hlo"]["targets"]
    matrix = doc["hlo"]["device_kinds"]["cpu"]

    # the acceptance criterion: a doctored device-kind row fails JL504
    # loudly, naming the dispatch and the kind
    doctored = copy.deepcopy(doc)
    doctored["hlo"]["device_kinds"]["cpu"]["serve/mf/b8"][
        "collective_bytes_total"] += 64
    _write_budget(tmp_path, doctored)
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), dict(rows),
                                             dict(matrix))
    assert [(f.code, f.func) for f in findings] == \
        [("JL504", "serve/mf/b8")], findings
    assert "'cpu'" in findings[0].message
    assert "kind-dependent" in findings[0].message

    # a missing matrix for the RUNNING kind is loud
    missing = copy.deepcopy(doc)
    del missing["hlo"]["device_kinds"]["cpu"]
    _write_budget(tmp_path, missing)
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), dict(rows),
                                             dict(matrix))
    assert [f.code for f in findings] == ["JL504"], findings
    assert "no pinned serving-dispatch row matrix" in findings[0].message

    # stale dispatch row under the running kind
    stale = copy.deepcopy(doc)
    stale["hlo"]["device_kinds"]["cpu"]["serve/mf/b999"] = \
        dict(matrix["serve/mf/b8"])
    _write_budget(tmp_path, stale)
    findings = checkers_hlo.check_hlo_budget(str(tmp_path), dict(rows),
                                             dict(matrix))
    assert any(f.code == "JL504" and f.func == "serve/mf/b999"
               and "stale" in f.message for f in findings)

    # a pinned kind this process cannot reach is CARRIED, never stale:
    # the TPU matrix a TPU run pinned must survive a cpu-only check
    foreign = copy.deepcopy(doc)
    foreign["hlo"]["device_kinds"]["TPU v99"] = {
        "serve/mf/b8": dict(matrix["serve/mf/b8"])}
    _write_budget(tmp_path, foreign)
    assert checkers_hlo.check_hlo_budget(str(tmp_path), dict(rows),
                                         dict(matrix)) == []


def test_hlo_allowlist_pool_split_regression():
    # one allowlist, one pool per engine: JL4xx -> memory, JL5xx -> hlo,
    # everything else -> ast; disjoint and exhaustive
    fake = {
        ("a.py", "f", "JL101"): "x" * 20,
        ("tools/collective_budget.json", "t", "JL402"): "y" * 20,
        ("tools/collective_budget.json", "t2", "JL501"): "z" * 20,
        ("tools/collective_budget.json", "t3", "JL503"): "w" * 20,
    }
    pools = split_allowlist(fake)
    assert set(pools) == {"ast", "memory", "hlo"}
    assert set(pools["ast"]) == {("a.py", "f", "JL101")}
    assert set(pools["memory"]) == {
        ("tools/collective_budget.json", "t", "JL402")}
    assert set(pools["hlo"]) == {
        ("tools/collective_budget.json", "t2", "JL501"),
        ("tools/collective_budget.json", "t3", "JL503")}
    merged = {}
    for p in pools.values():
        assert not set(merged) & set(p)          # disjoint
        merged.update(p)
    assert merged == fake                        # exhaustive

    # the regression this split exists for: a JL5xx entry must NOT reach
    # an AST-pool pass — there it matches no finding and would report
    # stale, failing every non-hlo stage of CI
    ast_findings = [Finding("JL101", "c", "a.py", 1, "f", "m")]
    active, stale = apply_allowlist(ast_findings, pools["ast"])
    assert active == [] and stale == []
    # ...and in ITS pool it suppresses the matching finding
    hlo_finding = Finding("JL501", "inserted-collective",
                          "tools/collective_budget.json", 1, "t2", "m")
    active, stale = apply_allowlist([hlo_finding], pools["hlo"])
    assert active == []                       # suppressed in its own pool
    assert len(stale) == 1 and "t3" in stale[0]   # unmatched JL503 entry
    # the committed allowlist partitions cleanly too
    committed = split_allowlist(ALLOWLIST)
    committed_merged = {}
    for p in committed.values():
        committed_merged.update(p)
    assert committed_merged == dict(ALLOWLIST)


def test_hlo_relowered_rows_match_committed_manifest(session):
    # the end-to-end gate: re-lowering every traced program (and the 6
    # serving dispatches on this backend) reproduces the committed hlo
    # section exactly, and the repo's own programs carry no JL501/JL503
    # hazards (no compiler-inserted collective kinds, no silently
    # replicated operands)
    rows = checkers_hlo.trace_hlo_all()
    kind_rows = checkers_hlo.serving_dispatch_rows()
    findings = checkers_hlo.check_hlo_budget(REPO, rows, kind_rows)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert len(rows) >= 32
    assert len(kind_rows) == 6
    assert checkers_hlo.check_hlo_hazards() == []


def test_hlo_build_section_carries_unreachable_kinds(session, tmp_path):
    # --update-budget on a cpu-only host must not DROP a TPU matrix a
    # TPU run pinned: build_hlo_section refreshes the running kind and
    # carries every other kind forward verbatim
    with open(os.path.join(REPO, checkers_hlo.BUDGET_FILE)) as f:
        doc = json.load(f)
    foreign_row = {"serve/mf/b8":
                   dict(doc["hlo"]["device_kinds"]["cpu"]["serve/mf/b8"])}
    doctored = copy.deepcopy(doc)
    doctored["hlo"]["device_kinds"]["TPU v99"] = foreign_row
    _write_budget(tmp_path, doctored)
    section = checkers_hlo.build_hlo_section(str(tmp_path))
    assert section["device_kinds"]["TPU v99"] == foreign_row
    assert set(section["device_kinds"]["cpu"]) == \
        set(doc["hlo"]["device_kinds"]["cpu"])
    assert section["targets"] == doc["hlo"]["targets"]


def test_hlo_only_flag_runs_exactly_one_engine(session, capsys):
    from tools.jaxlint.__main__ import main as jaxlint_main

    rc = jaxlint_main(["--hlo-only"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "hlo engine:" in out
    for banner in ("ast engine", "jaxpr engine", "gang engine",
                   "memory engine", "artifact engine"):
        assert banner not in out, out
    # exactly-one-engine contract: combining selectors is a usage error
    with pytest.raises(SystemExit):
        jaxlint_main(["--hlo-only", "--memory-only"])
    capsys.readouterr()
    with pytest.raises(SystemExit):
        jaxlint_main(["--hlo-only", "--update-budget"])
    capsys.readouterr()


def test_hlo_doctored_manifest_fails_jl502_in_json_stream(
        session, tmp_path, capsys):
    # end to end through the CLI: a doctored compiled-collective row in a
    # copied manifest surfaces as a machine-readable JL502 record on the
    # JSONL stream with the full record schema, and the exit goes nonzero
    with open(os.path.join(REPO, checkers_hlo.BUDGET_FILE)) as f:
        doc = json.load(f)
    doc["hlo"]["targets"]["serve_topk_mf"]["collective_bytes"][
        "all-to-all"] += 64
    _write_budget(tmp_path, doc)
    from tools.jaxlint.__main__ import main as jaxlint_main

    rc = jaxlint_main([str(tmp_path), "--hlo-only", "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    lines = [json.loads(line) for line in out.strip().splitlines()]
    hits = [r for r in lines if r["code"] == "JL502"]
    assert hits and hits[0]["func"] == "serve_topk_mf", out
    assert hits[0]["allowlisted"] is False
    assert "drift" in hits[0]["message"]
    assert {"file", "line", "code", "checker", "func", "message",
            "allowlisted"} <= set(hits[0])


