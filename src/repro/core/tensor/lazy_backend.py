"""Deferred/fusing backend — the ArrayFire-JIT analog (paper Fig. 2, §4.1.1).

Ops build an expression graph of :class:`LazyTensor` nodes instead of
executing.  Values are materialized only on user request (paper: "Tensor
values need only be materialized upon user request").  Materialization
routes the pending subgraph through the ``repro.compiler`` pipeline:

    trace → passes (cse / fold / dce / fuse) → lowering → execute

so fused elementwise clusters run as *generated* Pallas kernels (one
dispatch per cluster instead of one per op), and the whole run is
inspectable — the captured :class:`~repro.compiler.Graph`, per-pass node
deltas, and the lowered step list all surface through
``Session.describe()``.  The active :class:`~repro.runtime.CompilerPolicy`
selects the pipeline; an empty pipeline (``CompilerPolicy.legacy()``) is
the pre-compiler path — unrewritten node-at-a-time evaluation.

Compiled programs are cached by graph *signature* (op/attrs/edge
structure), so steady-state workloads skip pass+lowering work and reuse
the generated kernels (hitting jax's compilation cache).

The backend is also the framework's allocation-telemetry source (paper
§5.2.2): each materialization emits one alloc event per *surviving*
logical node and at most one free event per surviving interior node —
the alloc/free plan is computed after CSE/DCE, so merged or dead nodes
can never double-count.  Those traces drive the fragmentation-reduction
study in ``benchmarks/bench_fragmentation.py``.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from .backend import TensorBackend
from .jnp_backend import JnpBackend

_ELEMENTWISE = {
    "neg", "exp", "log", "sin", "cos", "tanh", "sqrt", "rsqrt", "abs", "sign",
    "floor", "erf", "logical_not", "isnan", "add", "sub", "mul", "div", "pow",
    "maximum", "minimum", "mod", "eq", "ne", "lt", "le", "gt", "ge",
    "logical_and", "logical_or", "where", "astype",
}

_ids = itertools.count()


def _freeze(x):
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return x


def _attrs(*items) -> tuple | None:
    """Static op parameters as a hashable tuple, or ``None`` (opaque —
    excluded from CSE/folding/program-caching) if anything unhashable
    (e.g. a traced index array) was captured."""
    items = tuple(_freeze(i) for i in items)
    try:
        hash(items)
    except TypeError:
        return None
    return items


class LazyTensor:
    """A deferred tensor: op + deps + (shape, dtype) metadata.

    This is the lazy backend's ``TensorAdapter`` (paper Listing 1): the
    per-tensor state a backend attaches to each tensor instance.
    ``attrs`` mirrors the op's static parameters for the compiler (see
    :func:`_attrs`); ``trace()`` lifts these nodes into the explicit IR.
    """

    __slots__ = ("op", "fn", "deps", "shape", "dtype", "value", "uid",
                 "attrs", "n_consumers", "__weakref__")

    def __init__(self, op: str, fn: Callable, deps: Sequence[Any],
                 shape, dtype, attrs: tuple | None = ()):
        self.op = op
        self.fn = fn
        self.deps = tuple(deps)
        self.shape = tuple(shape)
        self.dtype = dtype
        self.value = None
        self.uid = next(_ids)
        self.attrs = attrs
        self.n_consumers = 0
        for d in deps:
            if isinstance(d, LazyTensor):
                d.n_consumers += 1

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        out = 1
        for s in self.shape:
            out *= s
        return out

    def nbytes(self) -> int:
        return self.size * jnp.dtype(self.dtype).itemsize

    def __repr__(self):
        return (f"LazyTensor(op={self.op!r}, shape={self.shape}, "
                f"dtype={jnp.dtype(self.dtype).name}, "
                f"materialized={self.value is not None})")


class LazyBackend(TensorBackend):
    """Graph-building backend; materialization compiles the pending
    subgraph through ``repro.compiler`` under the session's policy."""

    name = "lazy"

    def __init__(self):
        self._eager = JnpBackend()
        # stats for the fusion benchmark / tests
        self.nodes_built = 0
        self.materialize_calls = 0
        self.ops_fused = 0
        self.kernels_generated = 0     # pallas cluster kernels built
        self.program_cache_hits = 0
        self.last_compile_report: dict | None = None
        self.last_compile_policy = None    # the policy that produced it
        self.last_analysis = None          # DiagnosticReport of last compile
        self._programs: dict[tuple, Any] = {}

    # -- graph construction ------------------------------------------------
    def _node(self, op: str, fn: Callable, deps: Sequence[Any],
              attrs: tuple | None = ()):
        struct_deps = [
            jax.ShapeDtypeStruct(d.shape, d.dtype) if isinstance(d, LazyTensor)
            else d
            for d in deps
        ]
        out = jax.eval_shape(fn, *struct_deps)
        self.nodes_built += 1
        return LazyTensor(op, fn, deps, out.shape, out.dtype, attrs=attrs)

    def _lift(self, x):
        """Wrap a concrete array as a leaf node."""
        if isinstance(x, LazyTensor):
            return x
        arr = jnp.asarray(x)
        leaf = LazyTensor("leaf", lambda: arr, (), arr.shape, arr.dtype,
                          attrs=None)
        leaf.value = arr
        return leaf

    # -- materialization: compile + execute --------------------------------
    def materialize(self, x):
        if not isinstance(x, LazyTensor):
            return jnp.asarray(x)
        if x.value is not None:
            return x.value
        self.materialize_calls += 1
        self._materialize([x])
        return x.value

    def materialize_many(self, xs):
        """Materialize several tensors as one jointly-compiled program
        (shared subexpressions are computed once)."""
        roots = [self._lift(x) for x in xs]
        pending = [r for r in roots if r.value is None]
        if pending:
            self.materialize_calls += 1
            self._materialize(pending)
        return [r.value for r in roots]

    def _materialize(self, roots: list[LazyTensor]) -> None:
        from repro import obs
        from repro.compiler import api as _api
        from repro.compiler import graph as _graph
        from repro.runtime import current_session

        from ..memory import telemetry

        sess = current_session()
        policy = sess.compiler
        analysis = sess.analysis
        tracer = obs.get_tracer(sess)
        with obs.span("compiler.materialize", "compiler", tracer,
                      roots=len(roots)):
            graph, sources = _graph.trace(roots)
            self.ops_fused += sum(1 for uid in graph.order
                                  if graph.nodes[uid].op in _ELEMENTWISE)

            exe = None
            key = None
            if policy.cache_programs:
                sig = graph.signature()
                if sig is not None:
                    # analysis level is part of the key: a program cached
                    # with checks off must not satisfy a strict session
                    key = (sig, policy, analysis)
                    exe = self._programs.get(key)
            if exe is not None:
                self.program_cache_hits += 1
                if tracer is not None:
                    tracer.metrics.counter(
                        "compiler.program_cache_hit").add()
            else:
                if tracer is not None:
                    tracer.metrics.counter(
                        "compiler.program_cache_miss").add()
                exe = _api.compile_graph(graph, policy, analysis=analysis)
                self.kernels_generated += exe.n_kernels
                if key is not None:
                    if len(self._programs) >= 256:  # bounded, FIFO eviction
                        self._programs.pop(next(iter(self._programs)))
                    self._programs[key] = exe
            self.last_compile_report = _api.describe_report(exe.report, exe)
            self.last_compile_policy = policy
            self.last_analysis = exe.diagnostics

            env = {cid: sources[cid].value for cid in exe.inputs}
            with obs.span("compiler.execute", "compiler", tracer,
                          dispatches=exe.n_dispatches):
                env = exe.run(env)

        # allocation telemetry over surviving logical nodes; uids are the
        # LazyTensor uids so events stay unique across materializations
        for cid, nbytes, tag in exe.allocs:
            lt = sources.get(cid)
            if lt is not None:
                telemetry.record_alloc(lt.uid, nbytes, tag=tag)
        for cid in exe.frees:
            lt = sources.get(cid)
            if lt is not None:
                telemetry.record_free(lt.uid)

        # write results back to every live handle (CSE-merged tensors
        # resolve to their surviving representative; cluster-internal
        # intermediates stay deferred and recompute on demand)
        for cid, lt in sources.items():
            if lt.value is None:
                rid = exe.resolve(cid)
                if rid in env:
                    lt.value = env[rid]

    # primitive ops are attached below, generated from the op tables


def _make_deferred_method(opname: str, arity: str):
    eager = JnpBackend()

    if arity == "unary":
        def method(self, x):
            x = self._lift(x)
            fn = getattr(eager, opname)
            return self._node(opname, fn, [x])
    elif arity == "binary":
        def method(self, lhs, rhs):
            lhs, rhs = self._lift(lhs), self._lift(rhs)
            fn = getattr(eager, opname)
            return self._node(opname, fn, [lhs, rhs])
    else:
        raise ValueError(arity)
    method.__name__ = opname
    return method


for _op in ["neg", "exp", "log", "sin", "cos", "tanh", "sqrt", "rsqrt", "abs",
            "sign", "floor", "erf", "logical_not", "isnan"]:
    setattr(LazyBackend, _op, _make_deferred_method(_op, "unary"))

for _op in ["add", "sub", "mul", "div", "pow", "maximum", "minimum", "mod",
            "eq", "ne", "lt", "le", "gt", "ge", "logical_and", "logical_or",
            "matmul"]:
    setattr(LazyBackend, _op, _make_deferred_method(_op, "binary"))


def _add_structured_methods():
    eager = JnpBackend()

    def full(self, shape, fill_value, dtype):
        return self._node("full", lambda: eager.full(shape, fill_value, dtype),
                          [], attrs=_attrs(shape, fill_value, jnp.dtype(dtype)))

    def arange(self, start, stop, step, dtype):
        return self._node("arange",
                          lambda: eager.arange(start, stop, step, dtype),
                          [], attrs=_attrs(start, stop, step, jnp.dtype(dtype)))

    def iota(self, dtype, shape, dimension):
        return self._node("iota", lambda: eager.iota(dtype, shape, dimension),
                          [], attrs=_attrs(jnp.dtype(dtype), shape, dimension))

    def random_uniform(self, key, shape, dtype, minval, maxval):
        return self._node(
            "random_uniform",
            lambda: eager.random_uniform(key, shape, dtype, minval, maxval),
            [], attrs=None)

    def random_normal(self, key, shape, dtype):
        return self._node(
            "random_normal", lambda: eager.random_normal(key, shape, dtype),
            [], attrs=None)

    def sum(self, x, axis, keepdims):
        x = self._lift(x)
        return self._node("sum", lambda v: eager.sum(v, axis, keepdims), [x],
                          attrs=_attrs(axis, keepdims))

    def max(self, x, axis, keepdims):
        x = self._lift(x)
        return self._node("max", lambda v: eager.max(v, axis, keepdims), [x],
                          attrs=_attrs(axis, keepdims))

    def min(self, x, axis, keepdims):
        x = self._lift(x)
        return self._node("min", lambda v: eager.min(v, axis, keepdims), [x],
                          attrs=_attrs(axis, keepdims))

    def prod(self, x, axis, keepdims):
        x = self._lift(x)
        return self._node("prod", lambda v: eager.prod(v, axis, keepdims), [x],
                          attrs=_attrs(axis, keepdims))

    def argmax(self, x, axis):
        x = self._lift(x)
        return self._node("argmax", lambda v: eager.argmax(v, axis), [x],
                          attrs=_attrs(axis))

    def cumsum(self, x, axis):
        x = self._lift(x)
        return self._node("cumsum", lambda v: eager.cumsum(v, axis), [x],
                          attrs=_attrs(axis))

    def reshape(self, x, shape):
        x = self._lift(x)
        return self._node("reshape", lambda v: eager.reshape(v, shape), [x],
                          attrs=_attrs(shape))

    def transpose(self, x, axes):
        x = self._lift(x)
        return self._node("transpose", lambda v: eager.transpose(v, axes), [x],
                          attrs=_attrs(axes))

    def broadcast_to(self, x, shape):
        x = self._lift(x)
        return self._node("broadcast_to",
                          lambda v: eager.broadcast_to(v, shape), [x],
                          attrs=_attrs(shape))

    def concatenate(self, xs, axis):
        xs = [self._lift(x) for x in xs]
        return self._node("concatenate",
                          lambda *vs: eager.concatenate(vs, axis), xs,
                          attrs=_attrs(axis))

    def slice(self, x, start, limit):
        x = self._lift(x)
        return self._node("slice", lambda v: eager.slice(v, start, limit), [x],
                          attrs=_attrs(start, limit))

    def dynamic_slice(self, x, start_indices, slice_sizes):
        x = self._lift(x)
        return self._node(
            "dynamic_slice",
            lambda v: eager.dynamic_slice(v, start_indices, slice_sizes), [x],
            attrs=_attrs(start_indices, slice_sizes))

    def dynamic_update_slice(self, x, update, start_indices):
        x, update = self._lift(x), self._lift(update)
        return self._node(
            "dynamic_update_slice",
            lambda v, u: eager.dynamic_update_slice(v, u, start_indices),
            [x, update], attrs=_attrs(start_indices))

    def pad(self, x, pad_width, value):
        x = self._lift(x)
        return self._node("pad", lambda v: eager.pad(v, pad_width, value), [x],
                          attrs=_attrs(pad_width, value))

    def where(self, cond, x, y):
        cond, x, y = self._lift(cond), self._lift(x), self._lift(y)
        return self._node("where", lambda c, a, b: eager.where(c, a, b),
                          [cond, x, y])

    def take(self, x, indices, axis):
        x, indices = self._lift(x), self._lift(indices)
        return self._node("take", lambda v, i: eager.take(v, i, axis),
                          [x, indices], attrs=_attrs(axis))

    def take_along_axis(self, x, indices, axis):
        x, indices = self._lift(x), self._lift(indices)
        return self._node(
            "take_along_axis",
            lambda v, i: eager.take_along_axis(v, i, axis), [x, indices],
            attrs=_attrs(axis))

    def scatter_add(self, x, indices, updates, axis):
        x, indices, updates = map(self._lift, (x, indices, updates))
        return self._node(
            "scatter_add",
            lambda v, i, u: eager.scatter_add(v, i, u, axis),
            [x, indices, updates], attrs=_attrs(axis))

    def flip(self, x, axis):
        x = self._lift(x)
        return self._node("flip", lambda v: eager.flip(v, axis), [x],
                          attrs=_attrs(axis))

    def sort(self, x, axis):
        x = self._lift(x)
        return self._node("sort", lambda v: eager.sort(v, axis), [x],
                          attrs=_attrs(axis))

    def top_k(self, x, k):
        # top_k returns a pair; materialize eagerly for simplicity
        v = self.materialize(self._lift(x))
        return eager.top_k(v, k)

    def astype(self, x, dtype):
        x = self._lift(x)
        return self._node("astype", lambda v: eager.astype(v, dtype), [x],
                          attrs=_attrs(jnp.dtype(dtype)))

    def stop_gradient(self, x):
        x = self._lift(x)
        return self._node("stop_gradient", lambda v: eager.stop_gradient(v),
                          [x])

    def dot_general(self, lhs, rhs, dimension_numbers, preferred_element_type):
        lhs, rhs = self._lift(lhs), self._lift(rhs)
        return self._node(
            "dot_general",
            lambda a, b: eager.dot_general(a, b, dimension_numbers,
                                           preferred_element_type),
            [lhs, rhs],
            attrs=_attrs(dimension_numbers, preferred_element_type))

    def conv2d(self, x, w, stride, padding):
        x, w = self._lift(x), self._lift(w)
        return self._node("conv2d",
                          lambda a, b: eager.conv2d(a, b, stride, padding),
                          [x, w], attrs=_attrs(stride, padding))

    for fname, f in list(locals().items()):
        if callable(f) and not fname.startswith("_"):
            setattr(LazyBackend, fname, f)


_add_structured_methods()

# Methods are attached post-hoc (generated from the primitive table), so the
# ABC machinery must be told the surface is now complete.
LazyBackend.__abstractmethods__ = frozenset()
