"""The plain reference against the program's pandas baseline on the 13
spec literal sets, and the float32 control against the reference."""

import hashlib
import json

import numpy as np
import pytest

from benchmarks.lib import compare, oracle, schedule
from benchmarks.tables import ssb_flat
from benchmarks.tests import keyed_table

ROWS, SEGMENTS, SEED = 120_000, 8, 2 ** 31 + 5


@pytest.fixture(scope="module")
def codes():
    return ssb_flat.table_codes(SEGMENTS, ROWS, SEED)


@pytest.fixture(scope="module")
def spec():
    return schedule.spec_queries(schedule.load_traffic("flights_c8"))


def test_oracle_agrees_with_ssb_baseline_on_the_spec_literals(codes, spec):
    import pandas as pd

    from pinot_tpu.tools import ssb, ssb_baseline

    frame = ssb_baseline.make_frame(ssb_flat.decode(codes))
    assert isinstance(frame, pd.DataFrame)
    assert [q["flight"] for q in spec] == list(ssb.QUERIES)
    for q in spec:
        # the family at SSB's literals is SSB's own text
        assert q["sql"].startswith(ssb.QUERIES[q["flight"]])
        want = ssb_baseline.run_query(frame, q["flight"])
        got = oracle.answer(ssb_flat, codes, q)
        same, gap = compare.rows_gap(got, want, q["order"])
        assert same and gap == 0.0, (q["flight"], got[:2], want[:2])


def test_table_is_a_function_of_the_seed(codes):
    again = ssb_flat.table_codes(SEGMENTS, ROWS, SEED)
    assert all(np.array_equal(codes[k], again[k]) for k in codes)
    other = ssb_flat.table_codes(SEGMENTS, ROWS, SEED + 1)
    assert not np.array_equal(codes["lo_revenue"], other["lo_revenue"])
    assert len(codes["lo_revenue"]) == ROWS


def test_float32_control_comes_out_not_correct(codes, spec):
    """The control (sums accumulated in float32, the step below the
    deployment's exact answers) fails the comparison's limit of 0."""
    cycle = [dict(q, id=i) for i, q in enumerate(spec)]
    want = {str(q["id"]): oracle.answer(ssb_flat, codes, q) for q in cycle}
    ctrl = {str(q["id"]): oracle.answer(ssb_flat, codes, q, np.float32)
            for q in cycle}

    def as_records(answers):
        import json
        return [{"index": q["id"], "status": 200, "body": json.dumps({
            "exceptions": [], "numServersQueried": 1,
            "numServersResponded": 1, "partialResult": False,
            "resultTable": {"rows": answers[str(q["id"])]}})}
            for q in cycle]

    sound = compare.compare(as_records(want), cycle, want)
    assert sound["responses_wrong"] == 0 and sound["max_abs_diff"] == 0.0
    control = compare.compare(as_records(ctrl), cycle, want)
    assert control["responses_wrong"] > 0 and control["max_abs_diff"] >= 1.0


@pytest.mark.parametrize("status,body,why", [
    (500, "{}", "HTTP 500"),
    (200, "not json", "not JSON"),
    (200, '{"exceptions": [{"message": "x"}]}', "exceptions"),
    (200, '{"exceptions": [], "numServersQueried": 2, '
          '"numServersResponded": 1, "partialResult": false, '
          '"resultTable": {"rows": []}}', "partial"),
    (200, '{"exceptions": [], "numServersQueried": 1, '
          '"numServersResponded": 1, "partialResult": true, '
          '"resultTable": {"rows": []}}', "partial"),
])
def test_a_response_that_breaks_a_guarantee_fails(status, body, why):
    _raw, broke = compare.check_response(status, body)
    assert broke is not None and why in broke


# --------------------------------------------------------------------------
# the reference narrows by an index of its own, and shares repeated masks
# --------------------------------------------------------------------------

KEYED_ROWS, KEYED_SEGMENTS = 500_000, 6        # 5,000 members


@pytest.fixture(scope="module")
def keyed():
    return keyed_table.table_codes(KEYED_SEGMENTS, KEYED_ROWS, SEED)


def full_mask(table_mod, cols, queries, dtype):
    return {str(q["id"]): oracle.answer(table_mod, cols, q, dtype)
            for q in queries}


def test_the_narrowed_path_equals_the_full_mask_row_for_row(keyed):
    queries = keyed_table.queries(KEYED_ROWS, 200, seed=3)
    out = oracle.answers(keyed_table, keyed, queries, control=True)
    assert out["oracle"]["narrowed"] == 200
    assert out["want"] == full_mask(keyed_table, keyed, queries, np.float64)
    assert out["control"] == full_mask(keyed_table, keyed, queries,
                                       np.float32)
    assert list(out["want"]) == [str(q["id"]) for q in queries]
    # the fixture is no empty comparison, and its control is a control
    by_kind = {}
    for q in queries:
        rows = out["want"][str(q["id"])]
        by_kind.setdefault(q["flight"], []).append(
            sum(r[-1] for r in rows))
    assert all(total == 0 for total in by_kind.pop("L4"))   # no such member
    assert all(any(totals) for totals in by_kind.values())
    assert out["control"] != out["want"]


@pytest.mark.parametrize("where,rows_kept", [
    ([["member", "=", 2 ** 40]], False),            # past the column's type
    ([["member", "in", -5, 17, 17, 2 ** 31]], True),
    ([["day", "between", 3, 9], ["member", "in", 4999, 0]], True),
])
def test_a_key_the_column_cannot_hold_keeps_no_row(keyed, where, rows_kept):
    ref = oracle.Reference(keyed_table, keyed)
    idx = ref.rows(where)
    assert ref.narrowed == 1
    assert np.array_equal(idx, np.flatnonzero(
        oracle._mask(keyed_table, keyed, where)))
    assert bool(len(idx)) is rows_kept


def test_only_a_key_column_is_ever_sorted(keyed):
    ref = oracle.Reference(keyed_table, keyed)
    assert ref.index("member") is not None
    order, ordered = ref.index("member")
    assert np.array_equal(keyed["member"][order], ordered)
    assert np.all(np.diff(ordered) >= 0)
    for column in ("day", "tag", "amount"):     # 365 days; text; a measure
        assert (ref.index(column) is None) == (column != "amount")
    # a range over the threshold with few distinct values is no key
    cols = {"sparse": (np.arange(1000, dtype=np.int32) % 7) * 100_000}
    assert oracle.Reference(keyed_table, cols).index("sparse") is None


# sha256 of json.dumps(answers_for(...)) as PR 32's oracle wrote it, want
# and control, at ROWS rows in SEGMENTS segments from SEED
PARENTS_DIGEST = {
    "spec": "e801bca4a416182875b821fae030e3e141fd17261473d5b655b9be371eb3bdd9",
    "cycle":
        "1aedbaebf790b0e214b81be51d251582c8b6227034920385abdcaf1ebfdb61b1",
}


@pytest.mark.parametrize("which", ["spec", "cycle"])
def test_ssb_answers_are_the_parents_byte_for_byte(which, spec):
    cycle = ([dict(q, id=i) for i, q in enumerate(spec)] if which == "spec"
             else schedule.build_cycle(schedule.load_traffic("flights_c8"),
                                       SEED))
    assert len(cycle) == {"spec": 13, "cycle": 104}[which]
    out = oracle.answers_for("ssb_flat", SEGMENTS, ROWS, SEED, cycle, True)
    told = out.pop("oracle")
    assert hashlib.sha256(json.dumps(out).encode()).hexdigest() \
        == PARENTS_DIGEST[which]
    assert told["strings"] == len(cycle) and told["narrowed"] == 0
    assert told["seconds"] >= told["table_s"] > 0


def test_no_ssb_conjunct_takes_the_index(codes, spec):
    """Every filter column of the 13 families has at most 84 values: the
    reference answers SSB by the full mask, as it always did."""
    cycle = schedule.build_cycle(schedule.load_traffic("flights_c8v"), SEED)
    ref = oracle.Reference(ssb_flat, codes, cycle)
    for q in cycle + spec:
        idx = ref.rows(q["where"])
        assert np.array_equal(idx, np.flatnonzero(
            oracle._mask(ssb_flat, codes, q["where"])))
    assert ref.narrowed == 0
    assert ref.sorted and all(v is None for v in ref.sorted.values())
    assert ref.masks_shared > len(cycle)    # most conjuncts repeat


def test_shared_masks_past_the_cap_go_and_the_answers_stay(codes,
                                                           monkeypatch):
    cycle = schedule.build_cycle(schedule.load_traffic("flights_c8"), SEED)
    want = oracle.answers(ssb_flat, codes, cycle, control=False)
    monkeypatch.setattr(oracle, "MASK_CACHE_BYTES", 3 * ROWS)   # 3 masks
    capped = oracle.answers(ssb_flat, codes, cycle, control=False)
    assert capped["want"] == want["want"]
    assert 0 < capped["oracle"]["masks_shared"] \
        < want["oracle"]["masks_shared"]
