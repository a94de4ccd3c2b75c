"""The plain reference against the program's pandas baseline on the 13
spec literal sets, and the float32 control against the reference."""

import numpy as np
import pytest

from benchmarks.lib import compare, oracle, schedule
from benchmarks.tables import ssb_flat

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
