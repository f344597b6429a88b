"""Ecosystem tests: cache serializer, scale datagen, debug dump, doc
freshness, ML export (SURVEY §2.8 equivalents)."""

import os

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.plan import TpuSession


@pytest.fixture(scope="module")
def session():
    return TpuSession()


def test_cache_roundtrip_and_reuse(session):
    df = session.create_dataframe(
        {"x": list(range(50)), "s": [f"s{i % 3}" for i in range(50)]})
    cached = df.filter(col("x") % 2 == 0).cache()
    from spark_rapids_tpu.cache import CachedRelation
    assert isinstance(cached.plan, CachedRelation)
    assert cached.count() == 25
    # downstream ops run on the cached blocks (both engines)
    agg = cached.group_by("s").agg(Sum(col("x")).alias("sx")).collect()
    assert sum(r["sx"] for r in agg) == sum(range(0, 50, 2))
    from spark_rapids_tpu.testing import assert_tpu_cpu_equal_df
    assert_tpu_cpu_equal_df(cached.select((col("x") + 1).alias("y")))


def test_cache_compresses(session):
    df = session.create_dataframe({"x": [7] * 10000})
    cached = df.cache()
    nbytes = sum(b.length for chunk in cached.plan.chunks
                 for b in chunk.values())
    assert nbytes < 10000 * 8 // 4  # constant column compresses well
    cached.unpersist()


def test_datagen_deterministic_chunks(session, tmp_path):
    from spark_rapids_tpu.datagen import (TableSpec, ColumnSpec,
                                          generate_chunk, generate_table,
                                          lineitem_spec)
    spec = lineitem_spec(10_000)
    a = generate_chunk(spec, 3, 1000)
    b = generate_chunk(spec, 3, 1000)  # regenerate independently
    assert (a.columns[0].values == b.columns[0].values).all()
    paths = generate_table(session, lineitem_spec(5000),
                           str(tmp_path / "li"), chunk_rows=2000)
    assert len(paths) == 3
    df = session.read.parquet(str(tmp_path / "li"))
    assert df.count() == 5000
    # discount values come from the choice list
    out = df.group_by("l_discount").agg(CountStar().alias("n")).collect()
    assert all(0 <= r["l_discount"] <= 0.10 for r in out)


def test_dump_and_replay(session, tmp_path):
    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.utils.dump import dump_batch, load_dump
    b = batch_from_pydict({"v": [1, None, 3], "s": ["a", "b", None]})
    path = dump_batch(b, str(tmp_path / "dumps"), prefix="repro")
    assert os.path.exists(path)
    back = load_dump(session, path).collect()
    assert [r["v"] for r in back] == [1, None, 3]


def test_docs_are_fresh():
    """docs regenerate to exactly what's committed (the reference
    CI-enforces generated docs the same way)."""
    from spark_rapids_tpu.conf import generate_docs
    from spark_rapids_tpu.plan.overrides import generate_supported_ops_doc
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "configs.md")) as f:
        assert f.read() == generate_docs(), \
            "docs/configs.md stale: run python tools/gen_docs.py"
    with open(os.path.join(root, "docs", "supported_ops.md")) as f:
        assert f.read() == generate_supported_ops_doc(), \
            "docs/supported_ops.md stale: run python tools/gen_docs.py"


def test_no_roofline_conf_is_registered_or_accepted():
    from spark_rapids_tpu import conf
    assert len(conf._REGISTRY) == 128
    assert not [k for k in conf._REGISTRY if k.startswith("srt.obs.roofline")]
    # a removed key is refused as any unknown srt.* key is
    for key in ("srt.obs.roofline.sampleEvery", "srt.no.such.key"):
        with pytest.raises(KeyError, match="unknown config"):
            conf.SrtConf({key: 1})


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPTS = sorted(
    os.path.join("tools", f) for f in os.listdir(os.path.join(_ROOT, "tools"))
    if f.endswith(".py")) + ["chip_smoke.py", "__graft_entry__.py"]


def _top_level_names(path):
    """Names a script binds at module level, read from its source."""
    import ast
    names, todo = set(), list(ast.parse(open(path).read()).body)
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            names.update(n.id for t in targets for n in ast.walk(t)
                         if isinstance(n, ast.Name))
        else:  # if / try / with / for at module level
            for field in ("body", "orelse", "finalbody", "handlers"):
                todo.extend(getattr(node, field, []))
    return names


@pytest.mark.parametrize("script", _SCRIPTS)
def test_script_compiles_and_what_it_imports_exists(script):
    """A script no tier-1 test runs (aot_cell_programs, serve_bench,
    chaos_check, mesh_nds, chip_smoke) still breaks when the package
    drops a name it uses. Nothing of the script is executed: its source
    is compiled, and every ``spark_rapids_tpu`` or sibling-script name
    it imports — and every attribute it reads off an imported package
    module — must exist."""
    import ast
    import importlib
    import types
    path = os.path.join(_ROOT, script)
    source = open(path).read()
    compile(source, path, "exec")
    siblings = {os.path.splitext(os.path.basename(s))[0]:
                os.path.join(_ROOT, s) for s in _SCRIPTS}
    missing, package_modules = [], {}

    def lookup(dotted):
        """The package module or attribute ``dotted`` names, or None."""
        try:
            return importlib.import_module(dotted)
        except ImportError:
            module, _, name = dotted.rpartition(".")
            return getattr(importlib.import_module(module), name, None)

    tree = ast.parse(source)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [(a.name, a.asname or a.name.split(".")[0], a.name
                      if a.asname else a.name.split(".")[0])
                     for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [(f"{node.module}.{a.name}", a.asname or a.name,
                      f"{node.module}.{a.name}") for a in node.names]
        else:
            continue
        for dotted, bound_as, bound_to in names:
            top, _, rest = dotted.partition(".")
            if top == "spark_rapids_tpu":
                if lookup(dotted) is None:
                    missing.append(dotted)
                elif isinstance(lookup(bound_to), types.ModuleType):
                    package_modules[bound_as] = bound_to
            elif top in siblings and rest \
                    and rest not in _top_level_names(siblings[top]):
                missing.append(dotted)
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in package_modules:
            dotted = f"{package_modules[node.value.id]}.{node.attr}"
            if lookup(dotted) is None:
                missing.append(dotted)
    assert not missing, f"{script} uses names that do not exist: {missing}"


def test_ml_export_device_arrays(session):
    import jax
    df = session.create_dataframe({"f1": [1.0, 2.0, 3.0],
                                   "label": [0, 1, 0]})
    arrs = df.to_device_arrays()
    f1, f1_valid = arrs["f1"]
    assert isinstance(f1, jax.Array)
    assert np.asarray(f1)[:3].tolist() == [1.0, 2.0, 3.0]
    assert np.asarray(f1_valid)[:3].all()


def test_api_validation_no_orphans():
    """tools/api_check.py (api_validation role): every declared
    expression is planner-reachable."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "api_check.py"),
         "--strict"], env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_extra_plugin_loader(tmp_path, monkeypatch):
    import sys

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.shims import load_extra_plugins
    mod = tmp_path / "my_srt_plugin.py"
    mod.write_text(
        "LOADED = []\n"
        "def init_plugin(conf):\n"
        "    LOADED.append(conf.get_raw('srt.sql.enabled')\n"
        "                  if hasattr(conf, 'get_raw') else True)\n"
        "    return 'plugin-object'\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    conf = SrtConf({"srt.plugins": "my_srt_plugin:init_plugin"})
    out = load_extra_plugins(conf)
    assert out == ["plugin-object"]
    import my_srt_plugin
    assert my_srt_plugin.LOADED


def test_crash_dump_and_replay(tmp_path):
    """srt.debug.dumpPath: a failing operator dumps every operator's
    last batch + the plan + the error; dumps replay through the reader
    (DumpUtils crash-dump role)."""
    import pytest

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr import col, raise_error
    from spark_rapids_tpu.expr.misc import RaiseErrorException
    from spark_rapids_tpu.plan import TpuSession
    dump_dir = str(tmp_path / "dumps")
    conf = SrtConf({"srt.debug.dumpPath": dump_dir})
    s = TpuSession(conf)
    df = s.create_dataframe({"v": [1.0, 2.0, 3.0]})
    # first projection succeeds (its batch is retained), second raises
    q = df.select((col("v") * 2).alias("w")) \
        .select("w", raise_error("kaboom").alias("e"))
    with pytest.raises(RaiseErrorException):
        q.collect()
    crashes = os.listdir(dump_dir)
    assert len(crashes) == 1
    crash = os.path.join(dump_dir, crashes[0])
    files = sorted(os.listdir(crash))
    assert "plan.txt" in files
    plan_txt = open(os.path.join(crash, "plan.txt")).read()
    assert "kaboom" in plan_txt and "Project" in plan_txt
    parquets = [f for f in files if f.endswith(".parquet")]
    assert parquets  # upstream operator batches captured
    from spark_rapids_tpu.utils.dump import load_dump
    replay = load_dump(TpuSession(), os.path.join(crash, parquets[0]))
    assert replay.collect()  # loads and executes
