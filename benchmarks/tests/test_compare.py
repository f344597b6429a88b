import importlib

import pandas as pd
import pytest

from benchmarks.harness import compare, control, lowprec

SPEC = {"keys": ["k"], "order_by": [["total", "desc"], ["k", "asc"]], "limit": 3}
LIMITS = {"float32": 1e-5, "float64": 1e-9}


def check(rows, ref, spec=SPEC, precision="float32", lane=None):
    c = compare.Comparison()
    c.add("q", rows, ref, {**spec, "precision": precision}, LIMITS[precision], lane=lane)
    return c


def frame(**columns):
    return pd.DataFrame(columns)


REF = frame(k=[1, 2, 3, 4, 5], total=[50.0, 40.0, 30.0, 30.0 * (1 + 1e-7), 10.0])


def test_right_answer_in_either_order_of_a_tie_is_correct():
    for third in (3, 4):  # groups 3 and 4 tie within the limit: either may take the last place
        rows = [{"k": 1, "total": 50.0}, {"k": 2, "total": 40.0}, {"k": third, "total": 30.0}]
        c = check(rows, REF)
        assert c.correct(LIMITS), c.notes
        assert c.numbers(LIMITS)["wrong_rows"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("rows, why", [
    ([{"k": 1, "total": 50.0}, {"k": 2, "total": 40.0}], "a row short"),
    ([{"k": 1, "total": 50.0}, {"k": 2, "total": 40.0}, {"k": 5, "total": 10.0}], "cut in the wrong place"),
    ([{"k": 2, "total": 40.0}, {"k": 1, "total": 50.0}, {"k": 3, "total": 30.0}], "out of order"),
    ([{"k": 1, "total": 50.0}, {"k": 2, "total": 40.0}, {"k": 9, "total": 30.0}], "a key the reference lacks"),
    ([{"k": 1, "total": 50.0}, {"k": 2, "total": 40.01}, {"k": 3, "total": 30.0}], "a float beyond the limit"),
    ([{"k": 1, "total": 50.0}, {"k": 2, "total": None}, {"k": 3, "total": 30.0}], "a NULL where a sum belongs"),
    ([], "nothing at all"),
])
def test_wrong_answers_are_not_correct(rows, why):
    assert not check(rows, REF).correct(LIMITS), why


def test_exact_columns_have_no_tolerance():
    ref = frame(k=["a"], n=[7], name=["x"])
    spec = {"keys": ["k"]}
    assert check([{"k": "a", "n": 7, "name": "x"}], ref, spec).correct(LIMITS)
    assert not check([{"k": "a", "n": 8, "name": "x"}], ref, spec).correct(LIMITS)
    assert not check([{"k": "a", "n": 7, "name": "y"}], ref, spec).correct(LIMITS)


def test_an_answer_from_a_lower_lane_than_stated_is_not_correct():
    """The program may not choose its own limit: a float64 query answered in the float32 lane is a
    departure even where its floats happen to agree; a higher lane than stated is none."""
    rows = [{"k": 1, "total": 50.0}, {"k": 2, "total": 40.0}, {"k": 3, "total": 30.0}]
    REF = frame(k=[1, 2, 3, 5], total=[50.0, 40.0, 30.0, 10.0])  # no near tie: float64 would not let one stand
    assert check(rows, REF, precision="float64", lane="float64").correct(LIMITS)
    low = check(rows, REF, precision="float64", lane="float32")
    assert not low.correct(LIMITS) and low.numbers(LIMITS)["lower_lane_answers"] == {"value": 1, "limit": 0}
    assert check(rows, REF, precision="float32", lane="float64").correct(LIMITS)
    # and the stated limit holds whatever the lane says: 1e-7 off is within float32's, not float64's
    off = [{"k": 1, "total": 50.0 * (1 + 1e-7)}, *rows[1:]]
    assert check(off, REF, precision="float32", lane="float32").correct(LIMITS)
    assert not check(off, REF, precision="float64", lane="float64").correct(LIMITS)


def test_bfloat16_rounds_to_nearest_even():
    assert lowprec.to_bfloat16([1.0, 1.00390625, 1.01171875]).tolist() == [1.0, 1.0, 1.015625]


@pytest.mark.parametrize("config_name, qids", [("tpch_tiny", ["q6", "q1", "q3"]), ("nds_tiny", ["q3", "q42", "q52"])])
def test_the_reference_in_the_precision_below_is_not_correct(tiny_data, config_name, qids):
    """The control: the reference put in the program's place, one precision below the one the
    configuration states for the query. It has to fail; the reference itself has to pass."""
    config, paths = tiny_data[config_name]
    family = importlib.import_module(f"benchmarks.configs.{config['family']}")
    limits = config["float_limits"]
    for qid in qids:
        spec = config["queries"][qid]
        ref = family.reference(qid, paths)
        same = compare.Comparison()
        same.add(qid, control.served(ref, spec), ref, spec, limits[spec["precision"]])
        assert same.correct(limits), (qid, same.notes)
        correct, numbers = control.control_numbers(config, family, [qid], paths)
        assert not correct, (qid, spec["precision"], numbers)
