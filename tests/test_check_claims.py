"""Claims honesty check (tools/check_claims.py) — tier-1.

A number README.md quotes from a committed manifest must be the manifest's.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import check_claims  # noqa: E402


def test_repo_claims_match_committed_records():
    assert check_claims.check(REPO) == []
    assert check_claims.main([REPO]) == 0


def test_parse_value_suffixes():
    assert check_claims.parse_value("1397") == 1397.0
    assert check_claims.parse_value("1.11M") == 1.11e6
    assert check_claims.parse_value("3.05B") == 3.05e9
    assert check_claims.parse_value("3.05G") == 3.05e9
    assert check_claims.parse_value("67.2M") == 67.2e6
    assert check_claims.parse_value("fast") is None


def test_drifted_claim_fails():
    claim = check_claims.Claim("x", "DOC.md", r"rate is (\S+) tokens/s",
                               ("row", "rate"))
    bench = {"row": {"rate": 100.0}}
    assert check_claims.check_claim(claim, "rate is 103 tokens/s",
                                    bench) is None
    v = check_claims.check_claim(claim, "rate is 150 tokens/s", bench)
    assert v and "out of" not in v and "150" in v     # drift is named
    # ±10% band is relative to the RECORDED value
    assert check_claims.check_claim(claim, "rate is 111 tokens/s",
                                    bench) is not None


def test_stale_entry_and_null_record_fail():
    claim = check_claims.Claim("x", "DOC.md", r"rate is (\S+) tokens/s",
                               ("row", "rate"))
    # reworded prose: the pattern no longer matches → loud
    v = check_claims.check_claim(claim, "throughput: 103 tokens/s", {})
    assert v and "not found" in v
    # null recorded value: a numeric claim on an unmeasured row must fail
    v = check_claims.check_claim(claim, "rate is 103 tokens/s",
                                 {"row": {"rate": None}})
    assert v and "unmeasured" in v
