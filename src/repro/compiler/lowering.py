"""Lowering: an optimized :class:`Graph` becomes an :class:`Executable`.

Each fused elementwise cluster is lowered to a *generated* Pallas kernel
(``repro.kernels.cluster`` synthesizes the body from the cluster's ops;
``interpret=True`` off-TPU).  Clusters the Pallas tiling cannot take — or
any cluster under ``lowering="jit"`` — get a per-cluster ``jax.jit`` of
the same synthesized body.  Residual nodes (reductions, matmuls, shape
ops) stay single dispatches.  ``lowering="eager"`` skips compilation
entirely: clusters execute as plain Python loops (debugging / the legacy
path).

The Executable also carries the *memory plan* for the lazy backend's
allocation telemetry (paper §5.2.2): one alloc per surviving logical node
and at most one free per surviving interior node — computed here, after
CSE/DCE, so merged or dead nodes can never double-count events.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from repro.kernels import cluster as cluster_kernels
from repro.runtime import current_session

from .graph import Graph
from .passes import PassStats


@dataclass
class OpStep:
    """A residual single-op dispatch."""

    uid: int
    inputs: tuple[int, ...]
    fn: Callable
    op: str


@dataclass
class ClusterStep:
    """One generated kernel covering a fused region."""

    fn: Callable                  # (*input arrays) -> tuple(outputs)
    inputs: tuple[int, ...]
    outputs: tuple[int, ...]
    kind: str                     # "pallas" | "jit" | "eager"
    n_ops: int = 0
    cluster_kind: str = "elementwise"   # Cluster.kind provenance


@dataclass
class Executable:
    """A lowered program: run ``steps`` over an env keyed by node uid."""

    steps: list[Any]
    consts: dict[int, Any]
    inputs: tuple[int, ...]
    outputs: tuple[int, ...]
    alias: dict[int, int]
    allocs: tuple[tuple[int, int, str], ...]   # (uid, nbytes, tag)
    frees: tuple[int, ...]
    report: list[PassStats] = field(default_factory=list)
    diagnostics: Any = None      # DiagnosticReport when analysis ran

    @property
    def n_dispatches(self) -> int:
        return len(self.steps)

    @property
    def n_kernels(self) -> int:
        return sum(1 for s in self.steps
                   if isinstance(s, ClusterStep) and s.kind == "pallas")

    def resolve(self, uid: int) -> int:
        while uid in self.alias:
            uid = self.alias[uid]
        return uid

    def run(self, env: dict[int, Any]) -> dict[int, Any]:
        """Execute into ``env`` (seeded with input values); returns the
        filled env — consts included, cluster intermediates omitted."""
        env.update(self.consts)
        for step in self.steps:
            if isinstance(step, OpStep):
                env[step.uid] = step.fn(*[env[d] for d in step.inputs])
            else:
                vals = step.fn(*[env[d] for d in step.inputs])
                for uid, v in zip(step.outputs, vals):
                    env[uid] = v
        return env

    def output_values(self, env: dict[int, Any]) -> list[Any]:
        return [env[self.resolve(o)] for o in self.outputs]

    def describe(self) -> dict:
        out = {"dispatches": self.n_dispatches,
               "pallas_kernels": self.n_kernels,
               "steps": [s.kind if isinstance(s, ClusterStep) else "op"
                         for s in self.steps],
               "clusters": [{"kind": s.cluster_kind, "lowering": s.kind,
                             "n_ops": s.n_ops}
                            for s in self.steps
                            if isinstance(s, ClusterStep)],
               "passes": [s.describe() for s in self.report]}
        if self.diagnostics is not None:
            out["diagnostics"] = self.diagnostics.counts()
        return out


def snapshot_logical(graph: Graph) -> list[tuple]:
    """Record the traced graph's logical structure *before* optimization,
    for the memory plan: ``(uid, inputs, nbytes, tag, is_input)``."""
    return [(uid, graph.nodes[uid].inputs, graph.nodes[uid].nbytes(),
             graph.nodes[uid].src_op, graph.nodes[uid].op == "input")
            for uid in graph.order]


def memory_plan(snapshot: list[tuple], graph: Graph
                ) -> tuple[tuple, tuple]:
    """Alloc/free schedule over *surviving* logical nodes.

    Computed from the pre-pass snapshot with the optimized graph's alias
    (CSE merges) and output liveness (DCE) applied — folding and fusion
    are execution strategies and must not change what the program
    logically allocates.  Exactly one alloc per surviving non-input node
    and at most one free per surviving node: a node is freed iff a *live*
    consumer uses it and it is not an output — so consumers merged by CSE
    or deleted by DCE can never double-count free events.
    """
    resolve = graph.resolve
    nodes: dict[int, tuple] = {}          # representative uid -> row
    inputs_of: dict[int, tuple[int, ...]] = {}
    order: list[int] = []
    for uid, inputs, nbytes, tag, is_input in snapshot:
        rep = resolve(uid)
        if rep in nodes:
            continue
        nodes[rep] = (nbytes, tag, is_input)
        inputs_of[rep] = tuple(resolve(d) for d in inputs)
        order.append(rep)
    out_set = {resolve(o) for o in graph.outputs}
    live: set[int] = set()
    stack = list(out_set)
    while stack:
        uid = stack.pop()
        if uid in live or uid not in nodes:
            continue
        live.add(uid)
        stack.extend(inputs_of[uid])
    consumed: set[int] = set()
    for uid in order:
        if uid in live:
            consumed.update(d for d in inputs_of[uid] if d != uid)
    allocs = []
    frees = []
    for uid in order:
        nbytes, tag, is_input = nodes[uid]
        if is_input or uid not in live:
            continue
        allocs.append((uid, nbytes, tag))
        if uid in consumed and uid not in out_set:
            frees.append(uid)
    return tuple(allocs), tuple(frees)


def lower(graph: Graph, policy: Any, report: list[PassStats] | None = None,
          interpret: bool | None = None,
          plan: tuple | None = None) -> Executable:
    """Lower an optimized graph under a ``CompilerPolicy``.

    ``plan`` is the ``memory_plan`` over the pre-pass snapshot; when
    absent (direct/testing use) it is derived from the optimized graph.
    """
    from repro import obs

    with obs.span("compiler.lower", "compiler",
                  nodes=len(graph.order)) as sp:
        exe = _lower(graph, policy, report, interpret=interpret, plan=plan)
        if sp is not None:
            sp.attrs.update({"dispatches": exe.n_dispatches,
                             "pallas_kernels": exe.n_kernels})
        return exe


def _lower(graph: Graph, policy: Any, report: list[PassStats] | None = None,
           interpret: bool | None = None,
           plan: tuple | None = None) -> Executable:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    vmem_limit = current_session().analysis.vmem_limit_bytes
    consts = {uid: graph.nodes[uid].value for uid in graph.order
              if graph.nodes[uid].op == "const"}

    # schedule over the *condensed* graph (clusters contracted to one
    # unit): a cluster executes atomically, so it runs only once every
    # external input is available — member order in `graph.order` can
    # interleave with outside producers.  Fusion legality guarantees the
    # condensed graph is acyclic, so Kahn's algorithm always completes.
    unit_of: dict[int, tuple] = {}
    unit_order: list[tuple] = []
    seen_units: set[tuple] = set()
    for uid in graph.order:
        node = graph.nodes[uid]
        if node.op in ("input", "const"):
            continue
        unit = (("c", node.cluster) if node.cluster is not None
                else ("n", uid))
        unit_of[uid] = unit
        if unit not in seen_units:
            seen_units.add(unit)
            unit_order.append(unit)
    unit_deps: dict[tuple, set[tuple]] = {u: set() for u in unit_order}
    for uid, unit in unit_of.items():
        for d in graph.nodes[uid].inputs:
            dep_unit = unit_of.get(d)
            if dep_unit is not None and dep_unit != unit:
                unit_deps[unit].add(dep_unit)
    scheduled: set[tuple] = set()
    schedule: list[tuple] = []
    pending = list(unit_order)
    while pending:
        progress = False
        remaining = []
        for u in pending:
            if unit_deps[u] <= scheduled:
                schedule.append(u)
                scheduled.add(u)
                progress = True
            else:
                remaining.append(u)
        pending = remaining
        if pending and not progress:
            raise AssertionError(
                "cycle in condensed graph — illegal fusion partition")

    steps: list[Any] = []
    for kind_tag, ident in schedule:
        if kind_tag == "n":
            node = graph.nodes[ident]
            steps.append(OpStep(ident, node.inputs, node.fn, node.op))
            continue
        cl = graph.clusters[ident]
        members = [graph.nodes[m] for m in cl.node_ids]
        ins = [graph.nodes[i] for i in cl.inputs]
        outs = [graph.nodes[o] for o in cl.outputs]
        if policy.lowering == "eager":
            fn = cluster_kernels.make_body(members, cl.inputs, cl.outputs)
            kind = "eager"
        elif policy.lowering != "auto":
            fn = cluster_kernels.build_jit_cluster(members, ins, outs)
            kind = "jit"
        elif cl.kind == "attention":
            # templated flash-attention; per-cluster jit when the tile
            # contract doesn't hold
            if cluster_kernels.attention_supported(
                    ins, cl.meta, on_tpu=not interpret):
                fn = cluster_kernels.build_attention_cluster(
                    ins, outs, cl.meta, interpret=interpret)
                kind = "pallas"
            else:
                fn = cluster_kernels.build_jit_cluster(members, ins, outs)
                kind = "jit"
        elif cl.kind == "epilogue":
            # the matcher only claims cones whose tiling plan validated
            fn = cluster_kernels.build_epilogue_cluster(
                members, ins, outs, cl.meta, interpret=interpret)
            kind = "pallas"
        elif cluster_kernels.pallas_supported(
                members, ins, len(outs), on_tpu=not interpret,
                vmem_limit=vmem_limit):
            fn = cluster_kernels.build_cluster_kernel(
                members, ins, outs, vmem_limit, interpret=interpret)
            kind = "pallas"
        else:
            fn = cluster_kernels.build_jit_cluster(members, ins, outs)
            kind = "jit"
        steps.append(ClusterStep(fn, cl.inputs, cl.outputs, kind,
                                 n_ops=len(cl.node_ids),
                                 cluster_kind=cl.kind))
    allocs, frees = plan if plan is not None else memory_plan(
        snapshot_logical(graph), graph)
    return Executable(steps, consts, graph.inputs, graph.outputs,
                      dict(graph.alias), allocs, frees,
                      report=list(report or []))
