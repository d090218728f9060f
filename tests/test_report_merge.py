"""RaptorReport.merge / merge_all edge cases (per-branch coverage).

The cross-shard reduction contract is documented in
``repro.core.memmode.RaptorReport`` but its edge branches — empty input,
single report, mismatched location tables, the no-truncated-locations
sentinel — were previously untested.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import memtrace, TruncationPolicy
from repro.core.memmode import RaptorReport


def _report(locs, flags, max_rel, op_counts):
    return RaptorReport(tuple(locs),
                        jnp.asarray(flags, jnp.int32),
                        jnp.asarray(max_rel, jnp.float32),
                        jnp.asarray(op_counts, jnp.int32))


def _get(x):
    return np.asarray(jax.device_get(x))


def test_merge_sums_and_maxes():
    a = _report(["l0", "l1"], [3, 0], [0.5, 0.0], [10, 4])
    b = _report(["l0", "l1"], [1, 2], [0.25, 1.5], [10, 4])
    m = a.merge(b)
    assert m.locations == ("l0", "l1")
    assert _get(m.flags).tolist() == [4, 2]
    assert _get(m.max_rel).tolist() == [0.5, 1.5]
    assert _get(m.op_counts).tolist() == [20, 8]


def test_merge_mismatched_locations_raises():
    """Reports from different computations must refuse to merge — including
    same-length tables whose location keys differ (the op_counts arrays
    would silently add misaligned rows otherwise)."""
    a = _report(["l0", "l1"], [1, 1], [0.1, 0.1], [2, 2])
    b = _report(["l0", "OTHER"], [1, 1], [0.1, 0.1], [2, 2])
    with pytest.raises(ValueError, match="location tables differ"):
        a.merge(b)
    # differing lengths hit the same guard, not a numpy broadcast error
    c = _report(["l0"], [1], [0.1], [2])
    with pytest.raises(ValueError, match="location tables differ"):
        a.merge(c)


def test_merge_all_empty_raises():
    with pytest.raises(ValueError, match="at least one report"):
        RaptorReport.merge_all([])


def test_merge_all_single_is_identity():
    a = _report(["l0"], [5], [0.75], [9])
    m = RaptorReport.merge_all([a])
    assert m is a  # single shard: no reduction work, no copy


def test_merge_all_many_is_left_fold():
    reports = [_report(["l0", "l1"], [i, 1], [0.1 * i, 0.2], [i, i])
               for i in range(1, 5)]
    m = RaptorReport.merge_all(reports)
    assert _get(m.flags).tolist() == [1 + 2 + 3 + 4, 4]
    assert _get(m.max_rel).tolist() == pytest.approx([0.4, 0.2])
    assert _get(m.op_counts).tolist() == [10, 10]


def test_merge_empty_sentinel_reports():
    """A computation with no truncated locations produces the sentinel
    single-row report; merging two of them must stay consistent rather than
    tripping on the placeholder table."""
    def f(x):
        return x * 2.0

    x = jnp.ones((4,), jnp.float32)
    _out, rep = memtrace(f, TruncationPolicy(rules=()), threshold=1e-3)(x)
    assert rep.locations == ("<no truncated locations>",)
    assert int(_get(rep.flags).sum()) == 0
    m = rep.merge(rep)
    assert m.locations == rep.locations
    assert int(_get(m.flags).sum()) == 0
    assert int(_get(m.op_counts).sum()) == 0


def test_merge_numpy_inputs_promote():
    """Host-side merging accepts numpy-stat reports (e.g. deserialized from
    another process) beside device ones: merge reads both as numpy."""
    a = RaptorReport(("l0",), np.asarray([2]), np.asarray([0.5], np.float32),
                     np.asarray([7]))
    b = _report(["l0"], [3], [0.125], [5])
    m = RaptorReport.merge_all([a, b])
    assert _get(m.flags).tolist() == [5]
    assert _get(m.max_rel).tolist() == [0.5]
    assert _get(m.op_counts).tolist() == [12]


def test_merge_counts_past_int32():
    """Counts merge in int64 on the host: three device reports of 2^31 - 1
    elements a location sum past 2^31, where the device's int32 would
    wrap; max_rel stays a float max."""
    big = [2**31 - 1, 2**30]
    a = _report(["l0", "l1"], big, [0.5, 0.25], big)
    m = RaptorReport.merge_all([a, a, a])
    for counts in (m.flags, m.op_counts):
        assert counts.dtype == np.int64
        assert counts.tolist() == [3 * (2**31 - 1), 3 * 2**30]
    assert m.max_rel.dtype == np.float32
    assert m.max_rel.tolist() == [0.5, 0.25]
