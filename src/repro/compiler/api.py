"""Public compiler API: ``repro.compile`` and the pipeline entry point.

``compile(fn)`` turns a function written against ``repro.core.tensor.ops``
into a compiled callable: the call is traced once per input signature
under a private lazy backend, optimized by the session's (or an explicit)
``CompilerPolicy`` pipeline, lowered to generated Pallas cluster kernels
(+ jit fallbacks), and cached — subsequent calls with the same shapes and
dtypes replay the compiled program directly.

    @repro.compile
    def f(x, y):
        return ops.tanh(ops.add(ops.mul(x, y), x))

    f(a, b)          # trace + optimize + lower
    f(a2, b2)        # cache hit: no tracing, reuses generated kernels

Concrete arrays that enter the graph mid-trace (closed-over ``jnp``
values, or results computed eagerly inside ``fn`` — ``ops.top_k``, a
nested ``materialize``) make the call *uncacheable*: it stays correct but
re-traces every time, because replaying such a value from the cache could
pin first-call results.  Constants built through ``ops`` (``ops.full``
etc.) trace as graph nodes and cache fine.  Graphs with opaque nodes
(e.g. random ops) likewise recompile on every call.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.runtime import CompilerPolicy, current_session, session

from . import graph as graph_mod
from . import lowering as lowering_mod
from .lowering import Executable, lower
from .passes import PassManager, PassStats


def optimize(graph: graph_mod.Graph, policy: CompilerPolicy
             ) -> list[PassStats]:
    """Run the policy's pass pipeline over ``graph`` in place."""
    return PassManager.from_policy(policy).run(graph)


def compile_graph(graph: graph_mod.Graph, policy: CompilerPolicy,
                  interpret: bool | None = None,
                  analysis: Any | None = None) -> Executable:
    """Optimize + lower a traced graph in one step.

    The telemetry memory plan is computed from the pre-pass logical
    structure (see :func:`repro.compiler.lowering.memory_plan`) so CSE/DCE
    shrink it but folding/fusion — execution strategies — do not.

    ``analysis`` (an :class:`~repro.runtime.AnalysisPolicy`) runs the
    static verifier over the result: at ``"strict"`` additionally between
    every pass and over the lowered step schedule + memory plan.  Findings
    at/above the policy's threshold raise
    :class:`~repro.analysis.AnalysisError`; the full report (including
    non-fatal lint) is attached as ``exe.diagnostics``.
    """
    from repro import obs

    with obs.span("compiler.compile", "compiler", nodes=len(graph.order)):
        snapshot = lowering_mod.snapshot_logical(graph)
        if analysis is not None and analysis.enabled:
            verify = analysis if analysis.strict else None
            report = PassManager.from_policy(policy).run(graph, verify=verify)
        else:
            report = optimize(graph, policy)
        plan = lowering_mod.memory_plan(snapshot, graph)
        exe = lower(graph, policy, report, interpret=interpret, plan=plan)
        if analysis is not None and analysis.enabled:
            from repro.analysis.suite import analyze_graph

            with obs.span("compiler.analyze", "compiler",
                          level=analysis.level):
                diags = analyze_graph(graph, analysis, exe=exe)
            exe.diagnostics = diags
            diags.raise_if_errors(analysis.error_threshold)
        return exe


def describe_report(report: list[PassStats], exe: Executable | None = None
                    ) -> dict:
    """JSON-able pipeline provenance (what ``Session.describe()`` embeds)."""
    out: dict[str, Any] = {"passes": [s.describe() for s in report]}
    if exe is not None:
        desc = exe.describe()
        out["dispatches"] = desc["dispatches"]
        out["pallas_kernels"] = desc["pallas_kernels"]
        out["clusters"] = desc["clusters"]
    return out


class CompiledFunction:
    """The callable ``repro.compile`` returns; one cache entry per input
    signature (shapes/dtypes of positional args + static kwargs)."""

    def __init__(self, fn: Callable, policy: CompilerPolicy | None = None,
                 check: str | None = None) -> None:
        self.fn = fn
        self.policy = policy
        self.check = check
        self._cache: dict[tuple, tuple] = {}
        self.trace_count = 0
        self.last_executable: Executable | None = None
        self.__name__ = getattr(fn, "__name__", "compiled")
        self.__doc__ = getattr(fn, "__doc__", None)
        if check is not None:
            # validate eagerly so a typo'd level fails at decoration time
            from repro.runtime import AnalysisPolicy

            AnalysisPolicy(level=check)

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def _policy(self) -> CompilerPolicy:
        return self.policy or current_session().compiler

    def _analysis(self) -> Any:
        base = current_session().analysis
        if self.check is None:
            return base
        return base.replace(level=self.check)

    def _key(self, args: tuple[Any, ...], kwargs: dict[str, Any]) -> tuple:
        sig = []
        for a in args:
            arr = jnp.asarray(a)
            sig.append((tuple(arr.shape), str(arr.dtype)))
        kw = tuple(sorted(kwargs.items()))
        try:
            hash(kw)
        except TypeError:
            raise TypeError(
                "repro.compile: keyword arguments must be hashable statics "
                "(they are part of the program cache key); pass arrays as "
                "positional arguments instead") from None
        # the analysis policy is part of the key: a program cached with
        # checks off must not satisfy a strict-session call unverified
        return (tuple(sig), kw, self._policy(), self._analysis())

    def _trace(self, args: tuple[Any, ...], kwargs: dict[str, Any],
               policy: CompilerPolicy, analysis: Any = None
               ) -> tuple[Executable, dict[int, int | None], dict[int, Any],
                          Any, bool]:
        from repro import obs
        from repro.core.tensor.lazy_backend import LazyBackend

        lb = LazyBackend()
        with obs.span("compiler.trace", "compiler",
                      fn=self.__name__), \
                session(backend=lb, compiler=policy):
            leaves = [lb._lift(jnp.asarray(a)) for a in args]
            # leaves minted from here on were created *during* the traced
            # call — if any of them ends up as a graph input, it is an
            # arg-dependent value computed eagerly mid-trace (ops.top_k,
            # a nested materialize, ...), and replaying it from the cache
            # would silently pin first-call results
            trace_watermark = lb._lift(jnp.zeros(())).uid
            out = self.fn(*leaves, **kwargs)
        out_flat, treedef = jax.tree_util.tree_flatten(
            out, is_leaf=lambda x: hasattr(x, "deps"))
        g, sources = graph_mod.trace(out_flat)
        self.trace_count += 1
        # map canonical input ids to arg positions (non-arg inputs are
        # captured constants: their trace-time value is replayed)
        by_lt_uid = {lt.uid: i for i, lt in enumerate(leaves)}
        arg_pos: dict[int, int | None] = {}
        captured: dict[int, Any] = {}
        mid_trace_capture = False
        for cid in g.inputs:
            src = sources[cid]
            pos = by_lt_uid.get(src.uid)
            arg_pos[cid] = pos
            if pos is None:
                captured[cid] = src.value
                mid_trace_capture |= src.uid > trace_watermark
        cacheable = (policy.cache_programs and not mid_trace_capture
                     and g.signature() is not None)
        exe = compile_graph(g, policy, analysis=analysis)
        return exe, arg_pos, captured, treedef, cacheable

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        from repro import obs

        tracer = obs.get_tracer()
        policy = self._policy()
        key = self._key(args, kwargs)
        entry = self._cache.get(key)
        if entry is None:
            if tracer is not None:
                tracer.metrics.counter("compiler.program_cache_miss").add()
            exe, arg_pos, captured, treedef, cacheable = self._trace(
                args, kwargs, policy, self._analysis())
            if cacheable:
                self._cache[key] = (exe, arg_pos, captured, treedef)
        else:
            exe, arg_pos, captured, treedef = entry
            if tracer is not None:
                tracer.metrics.counter("compiler.program_cache_hit").add()
        self.last_executable = exe
        env: dict[int, Any] = {}
        for cid in exe.inputs:
            pos = arg_pos.get(cid)
            env[cid] = (jnp.asarray(args[pos]) if pos is not None
                        else captured[cid])
        with obs.span("compiler.execute", "compiler", tracer,
                      fn=self.__name__, dispatches=exe.n_dispatches):
            outs = exe.output_values(exe.run(env))
        return jax.tree_util.tree_unflatten(treedef, outs)


def compile(fn: Callable | None = None, *,  # noqa: A001 - torch.compile idiom
            policy: CompilerPolicy | None = None,
            check: str | None = None
            ) -> "CompiledFunction | Callable[[Callable], CompiledFunction]":
    """Decorator: compile ``fn`` through the graph-IR pipeline.

    ``policy=None`` picks up the active session's ``CompilerPolicy`` at
    call time (so ``with repro.session(compiler=...)`` swaps the pipeline
    without retouching the function).

    ``check`` overrides the static-analysis level for this function only:
    ``"off"`` / ``"default"`` / ``"strict"`` (see
    :class:`repro.runtime.AnalysisPolicy`).  ``None`` inherits the active
    session's level; the session's other analysis knobs (VMEM budget)
    apply either way.
    """
    if fn is None:
        return lambda f: CompiledFunction(f, policy, check)
    return CompiledFunction(fn, policy, check)
