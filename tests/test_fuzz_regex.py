"""Regex fuzz lane (reference: sre_yield-driven enumeration in
integration_tests): randomly generated patterns from the transpiler's
supported grammar, random subject strings, NFA device semantics checked
against python ``re`` (the CPU oracle uses re too, so the comparison is
device-vs-re through the differential harness)."""

import random
import re
import string

import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.expr import col, lit
from spark_rapids_tpu.expr.core import Alias
from spark_rapids_tpu.expr.regex import (RegexUnsupported, RLike,
                                         transpile)
from spark_rapids_tpu.plan.session import TpuSession
from spark_rapids_tpu.testing import assert_tpu_cpu_equal_df

_R = random.Random(424242)
_ALPHABET = "abc01 .x"


def _rand_atom(depth):
    r = _R.random()
    if r < 0.35:
        return _R.choice("abc01x. ")  # literal (incl. '.' literal-ish)
    if r < 0.45:
        return _R.choice([r"\d", r"\w", r"\s", r"\D", r"\W", r"\S"])
    if r < 0.55:
        inner = "".join(_R.sample("abc013x", _R.randint(1, 4)))
        neg = "^" if _R.random() < 0.3 else ""
        return f"[{neg}{inner}]"
    if r < 0.62:
        return "."
    if depth >= 2:
        return _R.choice("abc")
    return f"({_rand_regex(depth + 1)})"


def _rand_regex(depth=0):
    n = _R.randint(1, 4)
    parts = []
    for _ in range(n):
        a = _rand_atom(depth)
        q = _R.random()
        if q < 0.2:
            a += _R.choice(["*", "+", "?"])
        elif q < 0.28:
            a += "{%d,%d}" % ((lambda lo: (lo, lo + _R.randint(0, 2)))
                              (_R.randint(0, 2)))
        parts.append(a)
    body = "".join(parts)
    if _R.random() < 0.2 and depth == 0:
        body = f"{body}|{_rand_regex(depth + 1)}"
    if _R.random() < 0.3 and depth == 0:
        body = "^" + body
    if _R.random() < 0.3 and depth == 0:
        body = body + "$"
    return body


def _rand_subjects(k):
    out = []
    for i in range(k):
        if i % 19 == 0:
            out.append(None)
        else:
            out.append("".join(
                _R.choice(_ALPHABET)
                for _ in range(_R.randint(0, 10))))
    return out


def _cases(n_patterns):
    cases = []
    tries = 0
    while len(cases) < n_patterns and tries < n_patterns * 20:
        tries += 1
        pat = _rand_regex()
        try:
            transpile(pat)       # must be device-supported
            re.compile(pat)      # and a valid python regex
        except (RegexUnsupported, re.error):
            continue
        cases.append(pat)
    assert len(cases) >= n_patterns, \
        f"could not generate enough supported patterns ({len(cases)})"
    return cases


_PATTERNS = _cases(60)


def test_pattern_pool_size():
    assert len(_PATTERNS) >= 50  # VERDICT floor: >50 generated cases


# slow: a fuzzer — ~100 s for the six chunks
@pytest.mark.slow
@pytest.mark.parametrize("chunk", range(6))
def test_rlike_fuzz_matches_python_re(chunk):
    """10 patterns x 40 subjects per chunk: device NFA simulation must
    agree with python re.search semantics (Spark RLIKE = unanchored
    find)."""
    session = TpuSession()
    subjects = _rand_subjects(40)
    df = session.create_dataframe({"s": subjects},
                                  schema=[("s", dt.STRING)])
    for pat in _PATTERNS[chunk * 10:(chunk + 1) * 10]:
        out = df.select(Alias(RLike(col("s"), pat), "m"))
        rows = out.collect()
        want = [None if s is None else re.search(pat, s) is not None
                for s in subjects]
        got = [r["m"] for r in rows]
        assert got == want, f"pattern {pat!r}: {got} != {want}"
