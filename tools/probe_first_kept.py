#!/usr/bin/env python3
"""Times ``ops/kernels.py::first_kept`` (one sort of the kept positions, since
PR 34) on the attached device beside what it replaced, the binary search of
the prefix sum, which lives on here alone as the reading's other side.

    python3 tools/probe_first_kept.py                    # the lookup join's shapes
    python3 tools/probe_first_kept.py 1048576:131072:12  # cap:out_capacity:one row kept in N

One JSON line a shape: for each formulation ``<name>_ms``, the host's
milliseconds a launch (the least of ``--repeats`` trains of ``--train``
launches enqueued back to back; never under the ~0.19 ms a dispatch costs),
``<name>_device_ms``, the program's median time on the device from a
profiler trace of one more train, and whether it returned the array the
search returns. The search is 0.1-0.23 ms a launch cheaper at 2^20 rows in
and at most 2^10 out (PR 34's reading): a shape no benchmark cell has, and a
prefix sum that compiles 17-33 s a shape against the sort's 2.3-3.6.
Nothing of the engine or the benchmark imports this.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spark_rapids_tpu.ops import kernels as K  # noqa: E402

#: cap, out_capacity, one row kept in N (0: every row kept)
SHAPES = (
    (1 << 20, 1 << 17, 12),    # q3's date join
    (1 << 20, 1 << 15, 60),    # q42 / q52's date join
    (1 << 17, 1 << 7, 700),    # q3's fused item join (joinOutCapacity 128)
    (1 << 15, 1 << 8, 100),    # q42 / q52's fused item join (256)
    (1 << 20, 1 << 20, 0),     # an unfiltered dimension: every row kept
    (1 << 20, 1 << 10, 2000),  # a small output: the search is cheaper here
    (1 << 20, 1 << 11, 1000),  # the smallest at which the sort is
)


def kept_by_search(keep: jnp.ndarray, out_capacity: int) -> jnp.ndarray:
    """``first_kept`` as it was up to PR 33: ``out_capacity`` binary
    searches over the prefix sum, log2(cap) dependent gathers each."""
    csum = jnp.cumsum(keep.astype(jnp.int32))
    want = jnp.arange(1, out_capacity + 1, dtype=jnp.int32)
    pos = jnp.searchsorted(csum, want, side="left").astype(jnp.int32)
    return jnp.clip(pos, 0, keep.shape[0] - 1)


FORMULATIONS = {"search": kept_by_search, "sort": K.first_kept}


def time_launches(fn, keep, train: int, repeats: int) -> float:
    jax.block_until_ready(fn(keep))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        outs = [fn(keep) for _ in range(train)]
        jax.block_until_ready(outs)
        best = min(best, (time.perf_counter() - t0) / train)
    return best * 1e3


def device_ms(trace_dir: str) -> dict:
    """Median milliseconds a program spent on the first device, by the
    jitted function's name, from the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spent = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for event in line.events:
                spent.setdefault(event.name.split("(")[0], []).append(
                    event.duration_ns / 1e6)
    return {name: float(np.median(ms)) for name, ms in spent.items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("shapes", nargs="*", help="cap:out_capacity:one-kept-in-N")
    ap.add_argument("--train", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=34)
    args = ap.parse_args()
    shapes = [tuple(int(x) for x in s.split(":")) for s in args.shapes] \
        or SHAPES
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    rng = np.random.default_rng(args.seed)
    for cap, out_capacity, one_in in shapes:
        host = np.ones(cap, bool) if one_in == 0 \
            else rng.integers(0, one_in, cap) == 0
        keep = jnp.asarray(host)
        line = {"cap": cap, "out_capacity": out_capacity,
                "kept": int(host.sum())}
        want, programs = None, {}
        for name, fn in FORMULATIONS.items():
            def program(k, fn=fn):
                return fn(k, out_capacity)
            # the trace names a program after its function
            program.__name__ = f"probe_{name}"
            programs[name] = jitted = jax.jit(program)
            t0 = time.perf_counter()
            got = np.asarray(jitted(keep))
            line[f"{name}_first_call_s"] = round(
                time.perf_counter() - t0, 2)
            if want is None:
                want = got
            line[f"{name}_equal"] = bool(np.array_equal(got, want))
            line[f"{name}_ms"] = round(time_launches(
                jitted, keep, args.train, args.repeats), 4)
        if dev.platform == "tpu":
            with tempfile.TemporaryDirectory() as trace_dir:
                with jax.profiler.trace(trace_dir):
                    for jitted in programs.values():
                        jax.block_until_ready(
                            [jitted(keep) for _ in range(args.train)])
                spent = device_ms(trace_dir)
            for name in programs:
                line[f"{name}_device_ms"] = round(
                    spent[f"jit_probe_{name}"], 4)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
