#!/usr/bin/env python
"""Claims honesty check: a number README.md quotes from a committed manifest
must be the manifest's.

Numeric prose drifts the moment a number is retyped instead of checked. Each
entry below names the doc, a regex whose single capture group is the claimed
number (K/M/G/B suffixes understood), the committed record that backs it
(``tools/collective_budget.json``: traced per-step collective bytes;
``tools/artifact_manifest.json``: the exported programs), where the value
lives in that record, and the relative band the claim must sit inside
(traced bytes and counts are exact: those claims use 0). Rates and times are
not pinned here: the chip's are in ``PERF_LEDGER.jsonl``, written by the
driver.

Failure modes are all loud:
  * claimed number outside the band          → the prose drifted (or the
    record moved and the prose was not updated with it);
  * regex no longer matches the doc          → stale checker entry (the
    claim was reworded without updating this table — same rule as
    lint_scatter's stale-allowlist check);
  * recorded value missing or null           → the claim asserts a number
    the committed record does not back.

Usage: ``python tools/check_claims.py [repo_root]`` — exits nonzero on any
violation. ``tests/test_check_claims.py`` runs it in tier-1.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Callable, List, NamedTuple, Optional, Union

_SUFFIX = {"K": 1e3, "M": 1e6, "G": 1e9, "B": 1e9}

BUDGET_FILE = "tools/collective_budget.json"


class Claim(NamedTuple):
    claim_id: str
    doc: str                    # repo-relative doc path
    pattern: str                # regex; group(1) = the claimed number
    source: Union[tuple, Callable]   # key path into the record, or a
    #   callable(record) -> float for derived quantities
    rel_tol: float = 0.10
    file: str = BUDGET_FILE     # which committed record backs the claim


CLAIMS: List[Claim] = [
    Claim("artifact_manifest_count", "README.md",
          r"content-hashes the (\S+) registry programs",
          lambda m: float(len(m["artifacts"])), rel_tol=0.0,
          file="tools/artifact_manifest.json"),
    Claim("comm_ingest_regroup_readme", "README.md",
          r"`ingest_coo_regroup` target, (\S+) B/step",
          ("targets", "ingest_coo_regroup", "bytes_per_step"),
          rel_tol=0.0),
]


def parse_value(text: str) -> Optional[float]:
    """'1397' → 1397.0; '1.11M' → 1.11e6; '3.05B'/'3.05G' → 3.05e9."""
    m = re.fullmatch(r"(\d+(?:\.\d+)?)([KMGB])?", text)
    if not m:
        return None
    return float(m.group(1)) * _SUFFIX.get(m.group(2) or "", 1.0)


def _lookup(record: dict, source) -> Optional[float]:
    if callable(source):
        try:
            return float(source(record))
        except (KeyError, TypeError, ZeroDivisionError):
            return None
    node = record
    for key in source:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return float(node) if isinstance(node, (int, float)) else None


def check_claim(claim: Claim, doc_text: str, record: dict) -> Optional[str]:
    """One claim against one doc + committed record; None = consistent."""
    m = re.search(claim.pattern, doc_text)
    if not m:
        return (f"{claim.doc}: claim '{claim.claim_id}' not found — the "
                f"prose was reworded; update its entry in "
                f"tools/check_claims.py (pattern {claim.pattern!r})")
    claimed = parse_value(m.group(1))
    if claimed is None:
        return (f"{claim.doc}: claim '{claim.claim_id}' captured "
                f"{m.group(1)!r}, not a number — fix the pattern")
    recorded = _lookup(record, claim.source)
    if recorded is None:
        return (f"{claim.doc}: claim '{claim.claim_id}' states "
                f"{m.group(1)} but the committed record has no measured "
                f"value for it (missing/null) — unmeasured rows must not be "
                f"quoted as numbers")
    if abs(claimed - recorded) > claim.rel_tol * abs(recorded):
        return (f"{claim.doc}: claim '{claim.claim_id}' states "
                f"{m.group(1)} but the committed record reads "
                f"{recorded:.4g} (> {100 * claim.rel_tol:.0f}% off) — "
                f"update the prose or the record")
    return None


def check(repo: str, claims: Optional[List[Claim]] = None) -> List[str]:
    records = {}
    docs = {}
    violations = []
    for claim in claims if claims is not None else CLAIMS:
        if claim.file not in records:
            with open(os.path.join(repo, claim.file)) as f:
                records[claim.file] = json.load(f)
        if claim.doc not in docs:
            with open(os.path.join(repo, claim.doc)) as f:
                docs[claim.doc] = f.read()
        v = check_claim(claim, docs[claim.doc], records[claim.file])
        if v:
            violations.append(v)
    return violations


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    repo = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    violations = check(repo)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} claim(s) out of sync with their records")
        return 1
    print(f"all {len(CLAIMS)} claims match their committed records")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
