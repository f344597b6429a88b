"""Each cell end to end at a tiny size through ``run.py --rehearse`` (which skips the look for a chip and
nothing else), and the same run with the timed path broken underneath: ``correct`` has to come out false.
The faults these cells can have: rows left out of what the engine scans, and an answer altered where it
is produced. (One chip, no state carried from step to step: the other two faults of the contract's list
do not exist here.)"""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.harness import engine

CELLS = ["tpch_tiny.q6_power", "nds_tiny.star_power"]


def rehearse(capsys, workload, seconds="1.5"):
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 7), "--seconds", seconds, "--trace", "0",
                   "--rehearse"])
    captured = capsys.readouterr()
    assert rc == 0
    result = json.loads(captured.out.strip().splitlines()[-1])
    # the numbers compared are the last lines of standard error, and the last key of the result line
    assert "wrong_rows = " in captured.err and list(result)[-1] == "compared"
    return result


@pytest.mark.parametrize("workload", CELLS)
def test_cell_end_to_end(capsys, workload):
    result = rehearse(capsys, workload)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == result["run"]["queries"] >= 3
    assert result["compared"]["wrong_rows"] == {"value": 0, "limit": 0}
    assert result["compared"]["rows_compared"]["value"] >= result["attempted"]
    assert result["device"]["platform"] == "cpu"
    # a rehearsal never prints under a metric's own name
    assert set(result["metrics"]) >= {"rehearsal.rows_per_s", "rehearsal.setup_s"}
    assert all(name.startswith("rehearsal.") for name in result["metrics"])
    assert result["run"]["compiles_in_window"] == 0
    assert not os.path.exists(os.path.join(run.WORK, workload, f"seed-{2**31 + 7}"))


@pytest.mark.parametrize("workload", CELLS)
def test_rows_left_out_of_the_scan_are_not_correct(capsys, monkeypatch, workload):
    real = engine.open_tables

    def half_the_files(session, paths, as_views):
        halved = {}
        for name, path in paths.items():
            files = sorted(os.listdir(path))
            assert name != "lineitem" and name != "store_sales" or len(files) >= 2
            halved[name] = path + ".half"
            os.makedirs(halved[name])
            for f in files[:max(1, len(files) // 2)]:
                shutil.copy(os.path.join(path, f), halved[name])
        return real(session, halved, as_views)

    monkeypatch.setattr(engine, "open_tables", half_the_files)
    result = rehearse(capsys, workload)
    assert result["correct"] is False and result["attempted"] >= 3


def altered(family, monkeypatch, alter, at_call):
    """Every query of the family answers as before, but for call number ``at_call`` (well past warm-up),
    whose first row is altered: one wrong answer among the window's many."""
    real, calls = family.make_query, [0]

    def make_query(session, tables, qid, entry):
        call = real(session, tables, qid, entry)

        def answer():
            rows = call()
            calls[0] += 1
            if calls[0] == at_call:
                alter(rows[0])
            return rows
        return answer

    monkeypatch.setattr(family, "make_query", make_query)


def scale_float(row):
    name = next(c for c, v in row.items() if isinstance(v, float))
    row[name] *= 1.001


def other_string(row):
    name = next(c for c, v in row.items() if isinstance(v, str))
    row[name] += "x"


@pytest.mark.parametrize("workload, alter", [(CELLS[0], scale_float), (CELLS[1], scale_float),
                                             (CELLS[1], other_string)])
def test_one_altered_answer_is_not_correct(capsys, monkeypatch, workload, alter):
    import importlib
    family = importlib.import_module("benchmarks.configs." + workload.split("_")[0])
    altered(family, monkeypatch, alter, at_call=25)
    result = rehearse(capsys, workload, seconds="3")
    assert result["attempted"] > 25 - 6  # the altered call fell inside the window
    assert result["correct"] is False
    wrong = result["compared"]
    assert wrong["wrong_rows"]["value"] > 0 or any(
        v["value"] > v["limit"] for k, v in wrong.items() if k.startswith("max_rel_err"))


@pytest.mark.parametrize("key, value", [("entry", "serve"), ("loop", "open"), ("streams", 4)])
def test_a_cell_that_asks_for_what_the_harness_lacks_does_not_run(capsys, monkeypatch, key, value):
    """``entry``, ``loop`` and ``streams`` of a workload file are read: a value nothing implements fails
    the run instead of being driven as one closed-loop stream under another name."""
    real = run.load_json

    def load_json(*parts):
        loaded = real(*parts)
        return {**loaded, key: value} if parts[0] == "workloads" else loaded

    monkeypatch.setattr(run, "load_json", load_json)
    argv = ["--workload", CELLS[1], "--seed", "5", "--seconds", "1", "--trace", "0", "--rehearse"]
    try:
        rc = run.main(argv)
    except (ImportError, ValueError):
        rc = 1
    assert rc != 0
    assert '"correct"' not in capsys.readouterr().out
