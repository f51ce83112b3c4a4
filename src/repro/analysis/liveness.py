"""Liveness / alias analysis over fusion clusters and lowered programs.

Three checkers:

* :func:`check_clusters` — audits the fusion partition *before* lowering:
  membership integrity, external-input/output edge sets recomputed from
  scratch (a member consumed outside the cluster but missing from
  ``Cluster.outputs`` would be silently dropped by lowering), **kind
  consistency** (``cluster.kind-mismatch`` — the declared ``Cluster.kind``
  must agree with the member ops, since lowering dispatches the kernel
  strategy on it: an attention cluster mislabeled elementwise would replay
  two matmuls through the whole-array body), atomicity
  (the condensed graph must be acyclic — Kahn's algorithm is re-run here,
  so an illegal partition is a diagnostic instead of a lowering crash),
  and a per-cluster **peak-live-bytes estimate against the VMEM budget**:
  the generated kernel holds every external input, every external output,
  and the live span of each intermediate simultaneously resident.
* :func:`check_executable` — audits a lowered step schedule: every read
  is preceded by its write (``exec.use-before-def``), no value is written
  twice (``exec.double-write`` — the defect a buggy CSE alias write-back
  introduces), and no cluster kernel writes a value it also reads
  (``exec.war`` — an in-kernel write-after-read hazard, since generated
  bodies read all inputs up front only by convention).
* :func:`check_memory_plan` — the alloc/free schedule invariants the
  selfcheck used to test by hand, as rules: unique allocs, unique frees,
  every free paired with an alloc.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any

from .diagnostics import DiagnosticReport, Severity

if TYPE_CHECKING:
    from repro.compiler.graph import Graph
    from repro.compiler.lowering import Executable
    from repro.runtime.policies import AnalysisPolicy


def _cluster_peak_bytes(graph: "Graph", node_ids: tuple[int, ...],
                        inputs: tuple[int, ...],
                        outputs: tuple[int, ...]) -> int:
    """Estimated peak VMEM residency of the generated cluster kernel.

    External inputs and outputs are resident for the whole kernel (read
    once up front / written once at the end); each intermediate is live
    from its defining member to its last in-cluster use.
    """
    members = set(node_ids)
    out_set = set(outputs)
    base = sum(graph.nodes[u].nbytes() for u in inputs)
    base += sum(graph.nodes[u].nbytes() for u in outputs)
    last_use: dict[int, int] = {}
    for i, uid in enumerate(node_ids):
        for d in graph.nodes[uid].inputs:
            if d in members:
                last_use[d] = i
    live = 0
    peak = 0
    dead_at: dict[int, list[int]] = {}
    for d, i in last_use.items():
        dead_at.setdefault(i, []).append(d)
    for i, uid in enumerate(node_ids):
        if uid not in out_set:                   # outputs already counted
            live += graph.nodes[uid].nbytes()
        peak = max(peak, live)
        for d in dead_at.get(i, ()):
            if d not in out_set and d in members:
                live -= graph.nodes[d].nbytes()
    return base + peak


def _kind_violation(kind: str, n_matmul: int, n_reduce: int,
                    meta: dict) -> str | None:
    """Why ``kind`` disagrees with the member ops; None when consistent."""
    if kind == "elementwise":
        if n_matmul or n_reduce:
            return (f"contains {n_matmul} matmul / {n_reduce} reduction "
                    "member(s) — the whole-array elementwise body would "
                    "replay them per-element")
    elif kind == "reduction":
        if n_matmul:
            return f"contains {n_matmul} matmul member(s)"
        if not n_reduce:
            return "contains no reduction member"
    elif kind == "epilogue":
        if n_matmul != 1:
            return (f"epilogue lowering fuses exactly one matmul, cluster "
                    f"has {n_matmul}")
    elif kind == "attention":
        if n_matmul != 2:
            return (f"attention template needs the QK^T and PV matmuls, "
                    f"cluster has {n_matmul}")
        if meta.get("mode") not in ("softmax", "sigmoid"):
            return f"meta mode {meta.get('mode')!r} is not a template mode"
    else:
        return f"unknown cluster kind {kind!r}"
    return None


def check_clusters(graph: "Graph", policy: "AnalysisPolicy | None" = None,
                   where: str | None = None) -> DiagnosticReport:
    """Verify the fusion partition, cluster-kind consistency, and
    per-cluster VMEM budgets."""
    from repro.compiler.graph import REDUCTION_OPS
    from repro.kernels.cluster import row_block
    from repro.runtime.policies import AnalysisPolicy

    policy = policy or AnalysisPolicy()
    report = DiagnosticReport()
    if not policy.enabled or not graph.clusters:
        return report
    consumers = graph.consumers()
    out_set = {graph.resolve(o) for o in graph.outputs}
    for cl in graph.clusters:
        members = set(cl.node_ids)
        prov = dict(cluster=cl.cid, where=where)
        for uid in cl.node_ids:
            node = graph.nodes.get(uid)
            if node is None:
                report.add("cluster.member-missing", Severity.ERROR,
                           f"member %{uid} is not in the graph", node=uid,
                           **prov)
                continue
            if node.cluster != cl.cid:
                report.add("cluster.member-mismatch", Severity.ERROR,
                           f"member %{uid} tagged cluster {node.cluster}",
                           node=uid, op=node.op, **prov)
        for uid in cl.inputs:
            if uid in members:
                report.add("cluster.input-internal", Severity.ERROR,
                           f"external input %{uid} is a cluster member",
                           node=uid, **prov)
            elif uid not in graph.nodes:
                report.add("cluster.input-missing", Severity.ERROR,
                           f"external input %{uid} is not in the graph",
                           node=uid, **prov)
        for uid in cl.outputs:
            if uid not in members:
                report.add("cluster.output-foreign", Severity.ERROR,
                           f"output %{uid} is not a cluster member",
                           node=uid, **prov)
        # kind consistency: lowering dispatches the kernel strategy on
        # Cluster.kind, so a mislabel silently picks the wrong lowering
        member_ops = [graph.nodes[u].op for u in cl.node_ids
                      if u in graph.nodes]
        why = _kind_violation(
            cl.kind, sum(op == "matmul" for op in member_ops),
            sum(op in REDUCTION_OPS for op in member_ops), cl.meta)
        if why is not None:
            report.add("cluster.kind-mismatch", Severity.ERROR,
                       f"cluster declared kind={cl.kind!r} but {why}",
                       **prov)
        # recompute the escape set: members consumed outside, or program
        # outputs, must be materialized by the kernel
        for uid in cl.node_ids:
            if uid not in graph.nodes:
                continue
            escapes = (uid in out_set
                       or any(c not in members for c in consumers.get(uid, ())))
            if escapes and uid not in cl.outputs:
                report.add("cluster.output-missing", Severity.ERROR,
                           f"member %{uid} is consumed outside the cluster "
                           "but is not a cluster output — lowering would "
                           "drop it", node=uid,
                           op=graph.nodes[uid].op, **prov)
        if all(u in graph.nodes for u in cl.node_ids + cl.inputs + cl.outputs):
            peak = _cluster_peak_bytes(graph, cl.node_ids, cl.inputs,
                                       cl.outputs)
            # a row-griddable cluster streams blocks sized to the budget,
            # so only its whole-array residency may exceed it
            if peak > policy.vmem_limit_bytes and row_block(
                    [graph.nodes[u] for u in cl.node_ids],
                    [graph.nodes[u] for u in cl.inputs], len(cl.outputs),
                    policy.vmem_limit_bytes) is None:
                report.add("vmem.over-budget", Severity.WARNING,
                           f"estimated peak residency {peak} B exceeds the "
                           f"per-cluster VMEM budget "
                           f"{policy.vmem_limit_bytes} B", **prov)
    # atomicity: the condensed graph (clusters contracted) must be acyclic
    unit_of: dict[int, tuple[str, int]] = {}
    for uid in graph.order:
        node = graph.nodes[uid]
        if node.op in ("input", "const"):
            continue
        unit_of[uid] = (("c", node.cluster) if node.cluster is not None
                        else ("n", uid))
    units = list(dict.fromkeys(unit_of.values()))
    deps: dict[tuple[str, int], set[tuple[str, int]]] = {u: set()
                                                         for u in units}
    for uid, unit in unit_of.items():
        for d in graph.nodes[uid].inputs:
            du = unit_of.get(d)
            if du is not None and du != unit:
                deps[unit].add(du)
    done: set[tuple[str, int]] = set()
    pending = list(units)
    while pending:
        ready = [u for u in pending if deps[u] <= done]
        if not ready:
            stuck = sorted(c for k, c in pending if k == "c")
            report.add("cluster.cycle", Severity.ERROR,
                       "condensed graph has a cycle — the fusion partition "
                       f"is not atomic (clusters involved: {stuck})",
                       where=where)
            break
        done.update(ready)
        pending = [u for u in pending if u not in done]
    return report


def check_executable(exe: "Executable",
                     where: str | None = None) -> DiagnosticReport:
    """Schedule verification of a lowered program (write-once, defs
    precede uses, no in-kernel write-after-read)."""
    from repro.compiler.lowering import ClusterStep, OpStep

    report = DiagnosticReport()
    defined: set[int] = set(exe.consts) | set(exe.inputs)
    for i, step in enumerate(exe.steps):
        war: set[int] = set()
        if isinstance(step, OpStep):
            reads, writes = step.inputs, (step.uid,)
            tag: dict[str, Any] = {"op": step.op}
        elif isinstance(step, ClusterStep):
            reads, writes = step.inputs, tuple(step.outputs)
            tag = {"op": f"cluster[{step.kind}]"}
            war = set(step.outputs) & set(step.inputs)
            for uid in sorted(war):
                report.add("exec.war", Severity.ERROR,
                           f"step {i} writes %{uid} which it also reads — "
                           "in-kernel write-after-read hazard", node=uid,
                           where=where, **tag)
        else:  # pragma: no cover - future step kinds
            continue
        # a WAR uid is by construction also use-before-def (not yet
        # written) or double-write (already written); report only the
        # root cause, not its cascade
        for d in reads:
            if d not in defined and d not in war:
                report.add("exec.use-before-def", Severity.ERROR,
                           f"step {i} reads %{d} before any step defines it",
                           node=d, where=where, **tag)
        for w in writes:
            if w in defined and w not in war:
                report.add("exec.double-write", Severity.ERROR,
                           f"step {i} writes %{w} which is already defined "
                           "— two writers for one logical value", node=w,
                           where=where, **tag)
            defined.add(w)
    for o in exe.outputs:
        if exe.resolve(o) not in defined:
            report.add("exec.undefined-output", Severity.ERROR,
                       f"program output %{o} is never defined", node=o,
                       where=where)
    return report


def check_memory_plan(allocs: tuple[tuple[int, int, str], ...],
                      frees: tuple[int, ...],
                      where: str | None = None) -> DiagnosticReport:
    """Alloc/free schedule invariants (exactly-once telemetry events)."""
    report = DiagnosticReport()
    alloc_uids = [a[0] for a in allocs]
    seen: set[int] = set()
    for uid in alloc_uids:
        if uid in seen:
            report.add("plan.double-alloc", Severity.ERROR,
                       f"%{uid} allocated twice", node=uid, where=where)
        seen.add(uid)
    fseen: set[int] = set()
    for uid in frees:
        if uid in fseen:
            report.add("plan.double-free", Severity.ERROR,
                       f"%{uid} freed twice", node=uid, where=where)
        fseen.add(uid)
        if uid not in seen:
            report.add("plan.free-unalloced", Severity.ERROR,
                       f"%{uid} freed but never allocated", node=uid,
                       where=where)
    return report
