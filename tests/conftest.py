"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's single-host test strategy (SURVEY §4: "no real
multi-node cluster is used anywhere") — all distributed paths are
exercised on a virtual device mesh.
"""

import os

# Must be set before jax initializes its backends. Tests run on a virtual
# 8-device CPU mesh (fast, deterministic); the chip is driven by
# chip_smoke.py, not by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# The suite is compile-bound (~60% of a test file's wall is XLA:CPU
# compiling programs that then run once over a few hundred rows), and it
# has one time limit for ~1300 tests: skip the backend's expensive
# optimization passes. What the tests check is semantics.
if "xla_backend_optimization_level" not in flags:
    flags += (" --xla_backend_optimization_level=0"
              " --xla_llvm_disable_expensive_passes=true")
os.environ["XLA_FLAGS"] = flags.strip()
# A program over the 8 virtual devices needs 8 of the CPU client's pool
# threads at once (its participants meet in a rendezvous), and the pool has
# max(cores, devices) of them: on an 8-core host two such programs in
# flight can each hold part of the pool and wait for the rest until XLA
# aborts the process ("expected 8 threads to join the rendezvous"; seen in
# test_mesh_spmd.py::test_nds_stage_identity, where unstack_shards slices
# every leaf of a sharded batch at once: PR 26). XLA sizes the pool from
# NPROC where it is set: room for four programs at a time.
os.environ.setdefault("NPROC", "32")

# XLA compile cache: the package's one rule (spark_rapids_tpu/__init__.py)
# — an already-set JAX_COMPILATION_CACHE_DIR is left alone; otherwise the
# CPU test lane gets a fixed directory of its own inside the checkout, a
# sibling of the package's .jax_cache, so CPU AOT entries and chip entries
# never mix. Set through the environment so the child processes tests
# start inherit it.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache_cpu_tests"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: kept out of the tier-1 run (-m 'not slow'): fuzzers, "
        "multi-process cluster legs, the long tail of the NDS suite")


_tests_run = [0]


@pytest.fixture(autouse=True)
def _release_programs_under_mmap_pressure():
    """The engine's own guard (plan/session.py::_mmap_guard) runs in
    session.execute; most tests drive exec trees directly and never
    pass it, and one process runs the whole suite — without this the
    run dies of mapping exhaustion (SIGSEGV inside jaxlib) about
    halfway through."""
    yield
    _tests_run[0] += 1
    if _tests_run[0] % 5:
        return
    from spark_rapids_tpu.plan.session import (mmap_pressure,
                                               release_compiled_programs)
    if mmap_pressure():
        release_compiled_programs()
