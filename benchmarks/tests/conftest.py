"""Run by hand, not part of tier-1:  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny_data(tmp_path_factory):
    """The tiny configurations' tables, generated once: {config name: (config, paths)}."""
    from benchmarks.harness import datagen
    out = {}
    for name in ("tpch_tiny", "nds_tiny"):
        config = load_config(name)
        paths, _ = datagen.generate(config, sorted(config["tables"]), 11, str(tmp_path_factory.mktemp(name)))
        out[name] = (config, paths)
    return out
