"""Shared-kernel jit registry: wrapper identity, isolation, no pinning,
and the one launch path.

The registry's contract (spark_rapids_tpu/jit_registry.py): structurally
equal programs share ONE jax.jit wrapper process-wide; unequal or
unencodable programs never alias; shared wrappers must not pin exec
trees (scan batches) in memory. Shared or private, a program is the same
thin object around ``jax.jit``: named ``jit_<label>``, launched inside
``launch.<label>``, its executables kept by jit's own cache and by
nothing else.
"""

import contextlib
import gc
import warnings
import weakref

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import jit_registry
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.vector import (ColumnVector, ColumnarBatch,
                                              live_mask)
from spark_rapids_tpu.exec.basic import BatchScanExec, FilterExec, ProjectExec
from spark_rapids_tpu.expr.core import col, lit


def _scan(n=8, cap=8):
    data = jnp.arange(cap, dtype=jnp.int64)
    lm = live_mask(cap, n)
    b = ColumnarBatch([ColumnVector(data, lm, dt.INT64)], ["x"], n)
    return BatchScanExec([b], [("x", dt.INT64)])


def test_equal_programs_share_one_wrapper():
    p1 = ProjectExec(_scan(), [(col("x") + lit(1)).alias("y")])
    p2 = ProjectExec(_scan(), [(col("x") + lit(1)).alias("y")])
    assert p1._jit is p2._jit


def test_different_programs_do_not_alias():
    p1 = ProjectExec(_scan(), [(col("x") + lit(1)).alias("y")])
    p2 = ProjectExec(_scan(), [(col("x") + lit(2)).alias("y")])
    assert p1._jit is not p2._jit


def test_filter_shares_on_equal_condition():
    f1 = FilterExec(_scan(), col("x") > lit(3))
    f2 = FilterExec(_scan(), col("x") > lit(3))
    f3 = FilterExec(_scan(), col("x") > lit(4))
    assert f1._jit is f2._jit
    assert f1._jit is not f3._jit


def test_shared_wrapper_does_not_pin_exec_tree():
    scan = _scan()
    ref = weakref.ref(scan)
    p = ProjectExec(scan, [(col("x") + lit(100)).alias("y")])
    del scan, p
    gc.collect()
    assert ref() is None, "registry must not keep the exec tree alive"


def test_shared_wrapper_computes_correctly_for_second_instance():
    # the wrapper registered by the FIRST instance serves the second;
    # results must depend only on the (equal) expression tree
    p1 = ProjectExec(_scan(), [(col("x") * lit(3)).alias("y")])
    p2 = ProjectExec(_scan(), [(col("x") * lit(3)).alias("y")])
    b = next(iter(p2.children[0]._batches))
    out = p2._jit(b)
    vals, mask = out.column("y").to_numpy(out.num_rows)
    assert list(vals[:4]) == [0, 3, 6, 9]


def test_uncachable_falls_back_to_private_jit():
    class Opaque:  # _enc cannot encode this
        pass

    def builder(_o):
        return lambda x: x + 1

    before = jit_registry.stats()["uncached"]
    f1 = jit_registry.shared_fn_jit(builder, Opaque())
    f2 = jit_registry.shared_fn_jit(builder, Opaque())
    assert f1 is not f2
    assert jit_registry.stats()["uncached"] >= before + 2
    assert int(f1(jnp.int32(1))) == 2


def test_stats_shape():
    s = jit_registry.stats()
    assert set(s) >= {"hits", "misses", "uncached", "entries"}


# --- one program class, one launch path ---

_COMPILE_REQUESTS = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, **_kw: _COMPILE_REQUESTS.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)


def _scale_builder(factor):
    return lambda x, bias=0: x * factor + bias


def _pair_sum_builder(_tag):
    return lambda x, y: x + 2 * y


def test_shared_and_private_programs_are_one_class(monkeypatch):
    from spark_rapids_tpu.robustness.admission import (QueryContext,
                                                       query_scope)
    shared = jit_registry.shared_fn_jit(_scale_builder, 7_310_001)
    private = jit_registry.named_jit(lambda x: x - 1, "Private.one")
    assert type(shared) is type(private) is jit_registry._NamedProgram
    assert jit_registry.shared_fn_jit(_scale_builder, 7_310_001) is shared
    x = jnp.arange(4, dtype=jnp.int32)
    assert "@jit__scale_builder " in shared.lower(x).as_text()
    assert "@jit_Private.one " in private.lower(x).as_text()
    ranges = []

    @contextlib.contextmanager
    def host_range(name):
        ranges.append(name)
        yield
    monkeypatch.setattr(jit_registry, "_host_range", host_range)
    query = QueryContext(query_id="q-launch-path")
    with query_scope(query):
        assert list(np.asarray(shared(x))) == [0, 7_310_001, 14_620_002,
                                               21_930_003]
        assert list(np.asarray(private(x))) == [-1, 0, 1, 2]
    assert ranges == ["launch._scale_builder", "launch.Private.one"]
    assert query.launches == 2 and query.dispatch_ns > 0
    shared(x)  # outside a query: launched, charged to nobody
    assert ranges[-1] == "launch._scale_builder" and query.launches == 2


def test_equal_avals_compile_once_a_new_shape_once_more():
    # what the compile ledger held (populated on a miss, not on a hit),
    # read where the benchmark reads it: jax.monitoring
    program = jit_registry.shared_fn_jit(_scale_builder, 7_310_002)
    a, b, wider = (jnp.asarray(np.arange(n, dtype=np.int32) + k)
                   for n, k in ((8, 0), (8, 5), (16, 0)))
    n0 = len(_COMPILE_REQUESTS)
    program(a)
    assert len(_COMPILE_REQUESTS) == n0 + 1
    program(b)
    again = jit_registry.shared_fn_jit(_scale_builder, 7_310_002)
    assert again is program
    again(a)
    assert len(_COMPILE_REQUESTS) == n0 + 1, "equal avals must not compile"
    program(wider)
    assert len(_COMPILE_REQUESTS) == n0 + 2


def _call_with_kwargs():
    program = jit_registry.shared_fn_jit(_scale_builder, 3)
    x = np.arange(5, dtype=np.int32)
    return program(jnp.asarray(x), bias=jnp.int32(4)), x * 3 + 4


def _call_under_enclosing_jit():
    program = jit_registry.shared_fn_jit(_scale_builder, 5)
    x = np.arange(6, dtype=np.int32)
    # the program sees tracers: jit inlines it into the outer program
    return jax.jit(lambda v: program(v) + 1)(jnp.asarray(x)), x * 5 + 1


def _call_with_donation():
    program = jit_registry.shared_fn_jit(_pair_sum_builder, "donating",
                                         donate_argnums=(0,))
    x, y = np.arange(7, dtype=np.int32), np.ones(7, dtype=np.int32)
    with warnings.catch_warnings():  # the CPU backend ignores donation
        warnings.simplefilter("ignore")
        return program(jnp.asarray(x), jnp.asarray(y)), x + 2 * y


def _call_with_17_shapes():
    program = jit_registry.shared_fn_jit(_scale_builder, 9)
    xs = [np.arange(n, dtype=np.int32) for n in range(1, 18)]
    return (np.concatenate([np.asarray(program(jnp.asarray(x)))
                            for x in xs]),
            np.concatenate(xs) * 9)


@pytest.mark.parametrize("call", [
    _call_with_kwargs, _call_under_enclosing_jit, _call_with_donation,
    _call_with_17_shapes], ids=lambda f: f.__name__.strip("_"))
def test_every_kind_of_call_gives_the_plain_functions_answer(call):
    got, expected = call()
    np.testing.assert_array_equal(np.asarray(got), expected)


_PICKY_TRACES = []


def _picky_builder(width):
    def picky(x):
        _PICKY_TRACES.append(x.shape)
        return x @ jnp.ones((width,), x.dtype)
    return picky


def test_an_aval_the_program_cannot_take_raises_once():
    program = jit_registry.shared_fn_jit(_picky_builder, 3)
    del _PICKY_TRACES[:]
    assert float(program(jnp.ones((3,), jnp.float32))) == 3.0
    with pytest.raises(TypeError):
        program(jnp.ones((4,), jnp.float32))
    assert _PICKY_TRACES == [(3,), (4,)], \
        "traced once, not retried on another path"


def test_released_programs_compile_again_and_answer_the_same():
    from spark_rapids_tpu.plan.session import release_compiled_programs
    program = jit_registry.shared_fn_jit(_scale_builder, 7_310_003)
    x = jnp.arange(8, dtype=jnp.int32)
    first = np.asarray(program(x))
    n0 = len(_COMPILE_REQUESTS)
    program(x)
    assert len(_COMPILE_REQUESTS) == n0
    release_compiled_programs()
    # the registry keeps the wrapper; jit's caches were all there was
    assert jit_registry.shared_fn_jit(_scale_builder, 7_310_003) is program
    np.testing.assert_array_equal(np.asarray(program(x)), first)
    assert len(_COMPILE_REQUESTS) == n0 + 1
