"""The control of a cell's ``correct``: the plain reference put in the program's place, computed one
precision below the one the configuration states for each query. It has to come out as not correct.

    python3 -m benchmarks.harness.control --workload tpch_sf1.q6_power --seeds 1 2 3

Touches no device: the reference is pandas on the host. Prints, for each seed, the numbers the
comparison read for the control beside their limits (the upper readings a limit is set under).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks.harness import compare, datagen, lowprec  # noqa: E402


def served(answer, spec: dict) -> list[dict]:
    """A full answer as an engine returns it: ordered and cut."""
    by = [c for c, _ in spec["order_by"]]
    if by:
        answer = answer.sort_values(by, ascending=[d == "asc" for _, d in spec["order_by"]])
    return answer.head(spec["limit"] or len(answer)).to_dict("records")


def control_numbers(config: dict, family, qids: list[str], paths: dict) -> tuple[bool, dict]:
    """Each query answered by the reference one precision below the one the configuration states for it:
    bfloat16 where it states float32, float32 where it states float64; held to the stated limit."""
    limits = config["float_limits"]
    comparison = compare.Comparison()
    for qid in qids:
        spec = config["queries"][qid]
        below = lowprec.BELOW[spec["precision"]]
        lowered = family.reference(qid, paths, precision=below)
        comparison.add(f"control ({qid}, {below} for {spec['precision']})", served(lowered, spec),
                       family.reference(qid, paths), spec, limits[spec["precision"]])
    return comparison.correct(limits), comparison.numbers(limits)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "workloads", f"{args.workload}.json")) as f:
        cell = json.load(f)
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    family = importlib.import_module(f"benchmarks.configs.{config['family']}")
    qids = list(dict.fromkeys(cell["queries"]))
    tables = sorted({t for q in qids for t in config["queries"][q]["scans"]})
    for seed in args.seeds:
        work = os.path.join(HERE, "work", args.workload, f"control-{seed}")
        shutil.rmtree(work, ignore_errors=True)
        try:
            paths, _ = datagen.generate(config, tables, seed, work)
            correct, numbers = control_numbers(config, family, qids, paths)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        print(json.dumps({"workload": args.workload, "seed": seed, "control_correct": correct, "compared": numbers}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
