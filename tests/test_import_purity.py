"""Importing the package must not touch a backend, and must follow the one
compile-cache rule (spark_rapids_tpu/__init__.py). Both are checked in child
processes: this process's jax is long since initialized."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code: str, **env_changes):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)


def test_import_touches_no_backend():
    """A launcher, client or probe that merely imports the package must
    not take the chip: with a platform name that does not exist, any
    backend initialization at import raises."""
    out = _child("import spark_rapids_tpu, spark_rapids_tpu.plan, "
                 "spark_rapids_tpu.serve",
                 JAX_PLATFORMS="no_such_platform")
    assert out.returncode == 0, out.stderr[-2000:]


def test_compile_cache_dir_follows_the_one_rule(tmp_path):
    show = ("import jax, spark_rapids_tpu; "
            "print(jax.config.jax_compilation_cache_dir)")
    given = str(tmp_path / "cache")
    out = _child(show, JAX_COMPILATION_CACHE_DIR=given)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == given
    out = _child(show, JAX_COMPILATION_CACHE_DIR=None)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == os.path.join(ROOT, ".jax_cache")
