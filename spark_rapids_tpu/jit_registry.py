"""Process-wide shared-kernel jit registry.

Each exec instance used to mint its own ``jax.jit`` wrappers in
``__init__``, so two structurally identical operators — the same
projection over the same schema in two different queries, the same
hash-partition function over the same table, the same join probe shape
— each paid a full trace + lower even though the persistent XLA cache
deduped the *compile*. Across a 99-query NDS sweep that re-trace cost
dominates wall-clock on the CPU lane (docs/PERF_NOTES.md). The registry
maps a STRUCTURAL key -> one jitted callable shared process-wide, so
trace/lower happens once per distinct (program, shapes) rather than
once per plan node.

Two entry points:

- ``shared_method_jit(obj, method, fields)`` — jit a *detached* bound
  method: a shell instance carrying only ``fields`` (copied off
  ``obj``) backs the traced function, so the registry never pins an
  exec tree (children, scan batches, broadcast state) in memory, and
  the key covers exactly the state the method may read. A field the
  method needs but that isn't listed fails loudly (AttributeError at
  trace time) — never a silent alias.
- ``shared_fn_jit(builder, *key_args)`` — jit ``builder(*key_args)``
  where ``builder`` is a MODULE-LEVEL factory whose output depends only
  on its arguments; the key is the builder's qualified name plus the
  structural encoding of ``key_args``.

Anything the structural encoder (plan/plan_cache._enc) cannot encode
falls back to a private ``jax.jit`` — unshared, never wrong.

Program names. Every program jitted here carries its structural label
as its name: the function handed to ``jax.jit`` is renamed to the label
(``FilterExec._filter``, ``_fused_program_builder``, a stage's label)
and runs under ``jax.named_scope`` of it, so the HLO module — what a
device trace and a compile log show — is ``jit_FilterExec._filter``
rather than ``jit__filter`` / ``jit_run`` / ``jit(<lambda>)``. A name
says which program SHAPE it is and nothing of one instance (no
fingerprint, capacity, address or plan id): it is the same from run to
run. Sharing is by registry key, never by name. ``named_jit`` gives the
few private jits outside the registry the same name and launch range.

Launch ranges. Each dispatch of a program runs inside a host range
``launch.<label>`` on the profiler's clock (obs/trace.py ``annotate``)
on the dispatching thread, around the asynchronous dispatch and not
around a sync; its wall time and a count are charged to the thread's
current query token (``dispatch_ns`` / ``launches`` of the query
record's ``phases``), with the thread's CPU time inside it
(``dispatch_cpu_ns``: the rest is time the launch stood off a CPU).

Read-backs. ``read_back`` is the inverse of a launch: every device
value the engine reads to the host on a query's path goes through it,
inside a ``device.read`` range, charged to the same token
(``readback_ns`` / ``readbacks``).

Reference role: the spark-rapids plugin loads/caches each cuDF kernel
once per JVM, not once per operator instance
(sql-plugin/src/main/scala/.../GpuOverrides.scala module-level kernel
dispatch); here the shared unit is the traced jaxpr.

Disable with ``SRT_JIT_REGISTRY=0`` (every call falls back to a
private ``jax.jit``) when isolating trace-level bugs.
"""

from __future__ import annotations

import functools
import os
import re
import threading
import time
from typing import Callable, Dict, Optional, Sequence

import jax
import numpy as np

from .obs.trace import annotate as _host_range
from .robustness.admission import current_query

_REGISTRY: Dict = {}
# RLock so the counter helpers may take it even when the caller
# already holds it for a lookup+insert critical section.
_LOCK = threading.RLock()
_STATS = {"hits": 0, "misses": 0, "uncached": 0}
# per defining module (builder's or method class's __module__), so a
# subsystem can report ITS share (e.g. the fused pipeline's reuse rate
# from module "spark_rapids_tpu.exec.fused")
_MODULE_STATS: Dict[str, Dict[str, int]] = {}


def _count(module: str, kind: str) -> None:
    """Count one hit/miss/uncached for ``module``. Takes ``_LOCK``
    itself (reentrant), so every mutation of ``_STATS``/
    ``_MODULE_STATS`` is race-free regardless of the call site."""
    with _LOCK:
        _STATS[kind] += 1
        m = _MODULE_STATS.setdefault(
            module, {"hits": 0, "misses": 0, "uncached": 0})
        m[kind] += 1

_ENABLED = os.environ.get("SRT_JIT_REGISTRY", "1") != "0"

# Soft cap: parameterized workloads (distinct literals, growing
# out_capacity buckets) mint unbounded distinct keys; past the cap the
# oldest entries are evicted FIFO (re-registration later is only a
# re-trace, never wrong). dict preserves insertion order.
_MAX_ENTRIES = int(os.environ.get("SRT_JIT_REGISTRY_MAX", 8192))


def _put(key, fn) -> None:
    while len(_REGISTRY) >= _MAX_ENTRIES:
        _REGISTRY.pop(next(iter(_REGISTRY)))
    _REGISTRY[key] = fn


def _encode(parts):
    """Structural key for ``parts`` or None when not safely encodable."""
    from .plan.plan_cache import Uncachable, _enc
    try:
        return _enc(parts)
    except Uncachable:
        return None
    except Exception:
        return None


def program_name(label: str) -> str:
    """``label`` cut to what an HLO module name keeps: letters, digits,
    ``_`` and ``.``."""
    return re.sub(r"[^A-Za-z0-9_.]", "_", label)


def _named(fn: Callable, label: str) -> Callable:
    """``fn`` under the name ``label`` for ``jax.jit``: the HLO module
    becomes ``jit_<label>`` and every op's ``op_name`` starts with it."""
    name = program_name(label)

    @functools.wraps(fn)
    def program(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return program


_LAUNCHING = threading.local()


def _dispatch(span: str, runner, args, kwargs):
    """Run one program launch inside its ``launch.<label>`` host range
    and charge its wall time, and the thread's CPU time within it, to
    the thread's current query. Dispatch is asynchronous: this is the
    host's cost of a launch, not device time. A program called while
    another is being traced is part of that launch: not counted."""
    if getattr(_LAUNCHING, "on", False):
        return runner(*args, **kwargs)
    _LAUNCHING.on = True
    t0 = time.perf_counter_ns()
    c0 = time.thread_time_ns()
    try:
        with _host_range(span):
            out = runner(*args, **kwargs)
    finally:
        _LAUNCHING.on = False
    cpu_ns = time.thread_time_ns() - c0
    query = current_query()
    if query is not None:
        query.count_launch(time.perf_counter_ns() - t0, cpu_ns)
    return out


_ON_HOST = (int, float, np.ndarray, np.generic)


def _held_on_host(value) -> bool:
    """A host value, or a device array whose host copy jax already holds
    (read before: ``ArrayImpl._npy_value``), whose read is no round trip."""
    return isinstance(value, _ON_HOST) \
        or getattr(value, "_npy_value", None) is not None


def _to_host(values: tuple) -> tuple:
    """``values`` with each device array as a numpy array, read with the
    device-to-host transfer guard lifted: several start their copies
    before the first is waited for (one round trip, as ``jax.device_get``
    makes it); one is a plain synchronous copy, without ``device_get``'s
    tree walk and asynchronous copy (a row count is read ~230 times a
    Q1 query: PERF.md, PR 35)."""
    with jax.transfer_guard_device_to_host("allow"):
        if len(values) > 1:
            for v in values:
                if isinstance(v, jax.Array):
                    v.copy_to_host_async()
        return tuple(np.asarray(v) if isinstance(v, jax.Array) else v
                     for v in values)


def read_back(*values) -> tuple:
    """Host values of ``values``, read from the device together: one
    explicit read (the transfer guard lifted for it alone) inside a
    ``device.read`` host range, its wall charged to the thread's current
    query (``readback_ns`` / ``readbacks``). The inverse of a launch, and
    the one way the engine reads a device value on a query's path:
    ``int()`` or ``np.asarray`` of a device array elsewhere is an
    implicit read that nothing counts, and that
    ``jax.transfer_guard_device_to_host("disallow")`` refuses
    (``tools/audit_readbacks.py``). Values already on the host, and
    device values read before, cost no round trip and count nothing; a
    read while a program is traced is that launch's."""
    if all(_held_on_host(v) for v in values):
        return _to_host(values)
    t0 = time.perf_counter_ns()
    with _host_range("device.read"):
        out = _to_host(values)
    query = current_query()
    if query is not None and not getattr(_LAUNCHING, "on", False):
        query.count_readback(time.perf_counter_ns() - t0)
    return out


def read_int(value) -> int:
    """``int(value)`` for a host or device scalar (a batch's
    ``num_rows``), a device one read through ``read_back``."""
    if isinstance(value, int):
        return value
    return int(read_back(value)[0])


class _NamedProgram:
    """Every program the registry hands out, shared or private: the
    ``jax.jit`` of the function renamed to its label, dispatched inside
    its ``launch.<label>`` range. jit's own cache keys the compiled
    executables by input avals; nothing is kept beside it."""

    __slots__ = ("fn", "_span")

    def __init__(self, fn, label: str):
        self.fn = fn
        self._span = "launch." + program_name(label)

    # attribute pass-through (e.g. .lower on the inner jit wrapper)
    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, *args, **kwargs):
        return _dispatch(self._span, self.fn, args, kwargs)


def named_jit(fn: Callable, label: str, **jit_kwargs) -> Callable:
    """``jax.jit(fn)`` named ``jit_<label>`` and launched inside
    ``launch.<label>``. Called directly by the sites that keep a private
    jit (a closure over live state no structural key covers); the shared
    entry points below put the same object in the registry."""
    return _NamedProgram(jax.jit(_named(fn, label), **jit_kwargs), label)


def _shared(key, module: str, label: str, make: Callable[[], Callable],
            jit_kwargs) -> Callable:
    """The program registered under ``key``; on a miss ``make()`` is
    jitted, registered and charged to ``module``."""
    with _LOCK:
        fn = _REGISTRY.get(key)
        if fn is not None:
            _count(module, "hits")
            return fn
        fn = named_jit(make(), label, **jit_kwargs)
        _put(key, fn)
        _count(module, "misses")
    return fn


def shared_method_jit(obj, method_name: str, fields: Sequence[str],
                      extra=(), **jit_kwargs) -> Callable:
    """Shared jit of ``type(obj).<method_name>`` bound to a detached
    shell holding only ``fields`` (copied from ``obj``).

    ``extra`` folds additional hashables (e.g. a static capacity) into
    the key when the method's builder varies on them.
    """
    cls = type(obj)
    label = f"{cls.__qualname__}.{method_name}"
    enc = _encode([getattr(obj, f) for f in fields]) if _ENABLED else None
    if enc is None:
        _count(cls.__module__, "uncached")
        return named_jit(getattr(obj, method_name), label, **jit_kwargs)
    key = (cls.__module__, cls.__qualname__, method_name, tuple(fields),
           enc, tuple(extra),
           tuple(sorted(jit_kwargs.items())) if jit_kwargs else ())

    def detached():
        shell = object.__new__(cls)
        for f in fields:
            setattr(shell, f, getattr(obj, f))
        return getattr(shell, method_name)
    return _shared(key, cls.__module__, label, detached, jit_kwargs)


def shared_fn_jit(builder: Callable, *key_args, **jit_kwargs) -> Callable:
    """Shared jit of ``builder(*key_args)``.

    ``builder`` must be module-level and pure: its returned function
    may depend only on ``key_args`` (and module globals that never
    change). Closures defined inside methods must NOT be passed here —
    refactor them into module-level factories first.
    """
    label = getattr(builder, "__qualname__", builder.__name__)
    enc = _encode(list(key_args)) if _ENABLED else None
    if enc is None:
        _count(builder.__module__, "uncached")
        return named_jit(builder(*key_args), label, **jit_kwargs)
    key = (builder.__module__, label, enc,
           tuple(sorted(jit_kwargs.items())) if jit_kwargs else ())
    return _shared(key, builder.__module__, label,
                   lambda: builder(*key_args), jit_kwargs)


def shared_stage_jit(build: Callable[[], Callable], key_parts,
                     module: str, label: str, **jit_kwargs) -> Callable:
    """Shared jit for a mesh STAGE program (plan/mesh_executor.py).

    Stage programs are built from closures over live plan nodes, so the
    ``shared_fn_jit`` contract (module-level builder, args-only key)
    cannot apply; instead the CALLER passes ``key_parts`` — the stage's
    structural signature (operator classes, expression reprs, schemas,
    mesh identity, growth factor, donation layout). Two plans whose
    stages match structurally share ONE jitted wrapper per stage
    shape — not per device, not per query — and jit's own aval cache
    handles row-capacity variation beneath that. Unencodable key parts
    fall back to a private jit (unshared, never wrong). ``build`` is
    only invoked on a miss.
    """
    enc = _encode(list(key_parts)) if _ENABLED else None
    if enc is None:
        _count(module, "uncached")
        return named_jit(build(), label, **jit_kwargs)
    key = (module, "stage_program", enc,
           tuple(sorted(jit_kwargs.items())) if jit_kwargs else ())
    return _shared(key, module, label, build, jit_kwargs)


def stats(module: Optional[str] = None) -> dict:
    """Registry counters; with ``module``, only the hits/misses/
    uncached charged to wrappers defined in that module (plus the
    module's live entry count). The whole snapshot is built under
    ``_LOCK`` — one consistent point in time, with the per-module
    dicts copied so callers never alias live counters."""
    with _LOCK:
        if module is not None:
            s = dict(_MODULE_STATS.get(
                module, {"hits": 0, "misses": 0, "uncached": 0}))
            s["entries"] = sum(1 for k in _REGISTRY if k[0] == module)
            return s
        s = dict(_STATS)
        s["entries"] = len(_REGISTRY)
        s["modules"] = {m: dict(d) for m, d in _MODULE_STATS.items()}
        return s


def clear() -> None:
    """Drop every shared wrapper (next use re-registers). The mmap
    guard (plan/session.py) calls jax.clear_caches(), which empties the
    wrappers' trace caches in place — that alone releases the compiled
    executables, so this is only for tests needing a cold registry."""
    with _LOCK:
        _REGISTRY.clear()
        _STATS.update(hits=0, misses=0, uncached=0)
        _MODULE_STATS.clear()
