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

Every shared program is wrapped in a :class:`_SharedProgram` — the
compile-ledger hook (obs/roofline.py): the wrapper AOT-compiles each
new input signature through ``trace()/lower()/compile()`` with each
phase wall-timed, captures XLA ``cost_analysis()`` flops/bytes, and
keeps the compiled executable for direct dispatch (so the AOT step
REPLACES jit's internal first-call trace, it does not duplicate it).
Launches are counted on the ledger entry, and with
``srt.obs.roofline.sampleEvery`` = N > 0 (off by default) every Nth
launch is timed with a device sync and joined with the program's
bytes/flops into achieved GB/s. Disable just the ledger with
``SRT_JIT_LEDGER=0`` (plain ``jax.jit`` wrappers, pre-ledger behavior).

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
record's ``phases``).

Reference role: the spark-rapids plugin loads/caches each cuDF kernel
once per JVM, not once per operator instance
(sql-plugin/src/main/scala/.../GpuOverrides.scala module-level kernel
dispatch); here the shared unit is the traced jaxpr.

Disable with ``SRT_JIT_REGISTRY=0`` (every call falls back to a
private ``jax.jit``) when isolating trace-level bugs.
"""

from __future__ import annotations

import functools
import hashlib
import os
import re
import threading
import time
from typing import Callable, Dict, Optional, Sequence

import jax

from .obs.trace import annotate as _host_range
from .robustness.admission import current_query

_REGISTRY: Dict = {}
# RLock so the counter helpers may take it even when the caller
# already holds it for a lookup+insert critical section.
_LOCK = threading.RLock()
_STATS = {"hits": 0, "misses": 0, "uncached": 0}
# per defining module (builder's or method class's __module__), so a
# subsystem can report ITS share — e.g. bench reads the fused-pipeline
# compile reuse rate from module "spark_rapids_tpu.exec.fused"
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
_LEDGER_ENABLED = os.environ.get("SRT_JIT_LEDGER", "1") != "0"

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


# --- compile ledger / roofline instrumentation (obs/roofline.py) ---

def _key_hash(key) -> str:
    """Stable short id for a structural key (ledger/event correlation
    across processes of the same build)."""
    try:
        return hashlib.sha1(repr(key).encode()).hexdigest()[:16]
    except Exception:
        return hex(id(key))[2:]


def _cost_of(compiled):
    """(flops, bytes_accessed) from ``compiled.cost_analysis()``, each
    None when the backend/jaxlib does not report it (CPU backends and
    older jaxlibs return None, a bare dict, or miss keys) — graceful
    degradation, never an error."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None

    def _num(k):
        v = ca.get(k)
        try:
            v = float(v)
        except (TypeError, ValueError):
            return None
        return v if v >= 0 else None
    return _num("flops"), _num("bytes accessed")


def _signature(args):
    """Hashable input signature (treedef + per-leaf aval incl. weak
    type) — the AOT executable cache key. Raises when any leaf has no
    aval (caller falls back to the plain jit path)."""
    from jax.api_util import shaped_abstractify
    leaves, treedef = jax.tree_util.tree_flatten(args)
    for leaf in leaves:
        if isinstance(leaf, jax.core.Tracer):
            # called under an enclosing trace (mesh lowering): jit
            # inlines fine, an AOT executable cannot run on tracers
            return None
    return treedef, tuple(shaped_abstractify(x) for x in leaves)


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


def _dispatch(span: str, runner, args, kwargs):
    """Run one program launch inside its ``launch.<label>`` host range
    and charge its wall time to the thread's current query. Dispatch is
    asynchronous: this is the host's cost of a launch, not device
    time."""
    t0 = time.perf_counter_ns()
    with _host_range(span):
        out = runner(*args, **kwargs)
    query = current_query()
    if query is not None:
        query.count_launch(time.perf_counter_ns() - t0)
    return out


class _NamedProgram:
    """A private jit (unshared, no ledger entry) that still has a stable
    program name and a ``launch.<label>`` range around each dispatch."""

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
    """``jax.jit(fn)`` for the sites that keep a private jit (a closure
    over live state no structural key covers): named ``jit_<label>`` and
    launched inside ``launch.<label>`` like a shared program."""
    return _NamedProgram(jax.jit(_named(fn, label), **jit_kwargs), label)


class _SharedProgram:
    """Callable wrapper around one shared jitted program that owns its
    compile-ledger entry.

    First call per input signature AOT-compiles (trace -> lower ->
    compile, each phase wall-timed, ``cost_analysis`` captured) and
    caches the compiled executable; later matching calls dispatch the
    executable directly — no re-trace, same steady-state as jit's own
    C++ cache. Unmatchable calls (kwargs, tracer args, signature-cache
    overflow, any AOT failure) fall back to the inner ``jax.jit``
    wrapper, so behavior never depends on the ledger. Every launch
    increments the entry's launch counter and runs inside the
    program's ``launch.<label>`` host range; with sampling on, every
    Nth launch (``roofline.sample_every()``) is synced and timed into
    the achieved-GB/s join.

    Holds only the jit wrapper, avals, and compiled executables —
    never the exec tree (the shell-detachment contract above stands).
    """

    #: distinct input signatures AOT-cached per program; beyond this
    #: (unbounded capacity buckets) calls run through the inner jit
    _SIG_CAP = 16

    __slots__ = ("fn", "entry", "_span", "_sigs", "_n", "_lock")

    def __init__(self, fn, entry):
        self.fn = fn
        self.entry = entry
        self._span = "launch." + program_name(entry.label)
        self._sigs: Dict = {}
        self._n = 0
        self._lock = threading.Lock()

    # attribute pass-through (e.g. .lower on the inner jit wrapper)
    def __getattr__(self, name):
        return getattr(self.fn, name)

    def hlo_texts(self) -> list:
        """Optimized (post-partitioning) HLO of every AOT-cached
        executable — where the collectives the compiler placed, and any
        ``tpu_custom_call`` kernels, can be read."""
        with self._lock:
            recs = [r for r in self._sigs.values() if r is not None]
        return [compiled.as_text() for compiled, _, _ in recs]

    def drop_executables(self) -> None:
        """Release AOT executables (mmap-guard / cache hygiene; the
        next call re-compiles through the ledger, which records it as
        the recompile it is)."""
        with self._lock:
            self._sigs.clear()

    def _aot(self, args):
        """Timed trace/lower/compile for ``args``; returns
        (compiled, bytes, flops) or None when AOT is not possible."""
        from .obs import roofline
        try:
            t0 = time.perf_counter_ns()
            tracer = getattr(self.fn, "trace", None)
            if tracer is not None:
                traced = tracer(*args)
                t1 = time.perf_counter_ns()
                lowered = traced.lower()
            else:  # older jax: trace folded into lower
                traced = None
                t1 = t0
                lowered = self.fn.lower(*args)
            t2 = time.perf_counter_ns()
            compiled = lowered.compile()
            t3 = time.perf_counter_ns()
        except Exception:
            return None
        flops, nbytes = _cost_of(compiled)
        try:
            roofline.record_compile(self.entry, trace_ns=t1 - t0,
                                    lower_ns=t2 - t1,
                                    compile_ns=t3 - t2, flops=flops,
                                    bytes_accessed=nbytes)
        except Exception:
            pass
        return compiled, nbytes, flops

    def _launch(self, runner, args, kwargs, nbytes, flops):
        from .obs import roofline
        entry = self.entry
        entry.count_launch()
        self._n += 1
        stride = roofline.sample_every()
        if stride > 0 and self._n % stride == 1 % stride:
            t0 = time.perf_counter_ns()
            out = _dispatch(self._span, runner, args, kwargs)
            try:
                jax.block_until_ready(out)
                roofline.record_sample(
                    entry, time.perf_counter_ns() - t0, nbytes, flops)
            except Exception:
                pass
            return out
        return _dispatch(self._span, runner, args, kwargs)

    def __call__(self, *args, **kwargs):
        if not kwargs:
            try:
                sig = _signature(args)
            except Exception:
                sig = None
            if sig is not None:
                rec = self._sigs.get(sig)
                if rec is None and sig not in self._sigs:
                    with self._lock:
                        rec = self._sigs.get(sig)
                        if rec is None and sig not in self._sigs:
                            if len(self._sigs) < self._SIG_CAP:
                                rec = self._aot(args)
                                self._sigs[sig] = rec
                if rec is not None:
                    compiled, nbytes, flops = rec
                    try:
                        return self._launch(compiled, args, {},
                                            nbytes, flops)
                    except (TypeError, ValueError):
                        # aval/placement mismatch the signature missed:
                        # the inner jit re-specializes, always right
                        pass
        # fallback: kwargs, tracers, unsignable leaves, sig overflow,
        # or failed AOT — plain shared jit, still launch-counted (no
        # per-sig cost known, so samples join with bytes=None)
        return self._launch(self.fn, args, kwargs, None, None)


def _wrap_program(fn, key, module: str, label: str):
    """Attach the compile-ledger wrapper to a fresh shared jit (miss
    path). With the ledger disabled the raw jit is stored instead."""
    if not _LEDGER_ENABLED:
        return fn
    try:
        from .obs import roofline
        entry = roofline.ensure_entry(_key_hash(key), module, label)
    except Exception:
        return fn
    return _SharedProgram(fn, entry)


def annotate(fn, display: str) -> None:
    """Set the operator-facing display label on a shared program's
    ledger entry (e.g. the fused chain description). No-op for plain
    jits (uncached fallbacks, ledger disabled)."""
    entry = getattr(fn, "entry", None)
    if entry is not None:
        entry.display = str(display)


def rebind_ledger_entries() -> None:
    """Give every live wrapper a FRESH ledger entry under its original
    key. ``roofline.reset()`` (tests) calls this after dropping the
    ledger: without it, wrappers registered before the reset would keep
    counting into orphaned entries the new ledger never sees."""
    with _LOCK:
        fns = [f for f in _REGISTRY.values()
               if isinstance(f, _SharedProgram)]
    try:
        from .obs import roofline
    except Exception:
        return
    for f in fns:
        old = f.entry
        new = roofline.ensure_entry(old.key, old.module, old.label)
        if new is not old:
            new.display = old.display
            f.entry = new


def release_executables() -> None:
    """Drop every shared program's AOT executables (companion to
    ``jax.clear_caches()`` in the mmap guard and bench sweeps — the
    wrappers hold compiled programs jax's own caches do not track).
    Ledger counters and the registry itself survive; next launches
    re-compile and are ledgered as recompiles."""
    with _LOCK:
        fns = list(_REGISTRY.values())
    for fn in fns:
        drop = getattr(fn, "drop_executables", None)
        if drop is not None:
            try:
                drop()
            except Exception:
                pass


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
    with _LOCK:
        fn = _REGISTRY.get(key)
        if fn is not None:
            _count(cls.__module__, "hits")
            return fn
        shell = object.__new__(cls)
        for f in fields:
            setattr(shell, f, getattr(obj, f))
        fn = _wrap_program(
            jax.jit(_named(getattr(shell, method_name), label),
                    **jit_kwargs), key, cls.__module__, label)
        _put(key, fn)
        _count(cls.__module__, "misses")
    return fn


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
    with _LOCK:
        fn = _REGISTRY.get(key)
        if fn is not None:
            _count(builder.__module__, "hits")
            return fn
        fn = _wrap_program(
            jax.jit(_named(builder(*key_args), label), **jit_kwargs),
            key, builder.__module__, label)
        _put(key, fn)
        _count(builder.__module__, "misses")
    return fn


def shared_stage_jit(build: Callable[[], Callable], key_parts,
                     module: str, label: str, **jit_kwargs) -> Callable:
    """Shared jit for a mesh STAGE program (plan/mesh_executor.py).

    Stage programs are built from closures over live plan nodes, so the
    ``shared_fn_jit`` contract (module-level builder, args-only key)
    cannot apply; instead the CALLER passes ``key_parts`` — the stage's
    structural signature (operator classes, expression reprs, schemas,
    mesh identity, growth factor, donation layout). Two plans whose
    stages match structurally share ONE jitted wrapper and ONE
    compile-ledger entry per stage shape — not per device, not per
    query — and jit's own aval cache handles row-capacity variation
    beneath that. Unencodable key parts fall back to a private jit
    (unshared, never wrong). ``build`` is only invoked on a miss.
    """
    enc = _encode(list(key_parts)) if _ENABLED else None
    if enc is None:
        _count(module, "uncached")
        return named_jit(build(), label, **jit_kwargs)
    key = (module, "stage_program", enc,
           tuple(sorted(jit_kwargs.items())) if jit_kwargs else ())
    with _LOCK:
        fn = _REGISTRY.get(key)
        if fn is not None:
            _count(module, "hits")
            return fn
        fn = _wrap_program(jax.jit(_named(build(), label), **jit_kwargs),
                           key, module, label)
        _put(key, fn)
        _count(module, "misses")
    return fn


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
