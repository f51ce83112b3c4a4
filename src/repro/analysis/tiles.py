"""Tile-divisibility and masked-OOB checks for Pallas kernel launches.

A ``pallas_call`` whose grid × block extent disagrees with the array
extent reads or writes out of bounds unless the kernel body masks the
overhang.  The hand-written kernels in ``repro/kernels/`` each declare a
**contract** here — the same clamping arithmetic their launch wrappers
perform, plus which dimensions are masked in-kernel — so a bad launch
shape is a structured diagnostic *before* the kernel traps (or worse,
silently wraps under ``interpret=True``).

:func:`check_kernel_call` evaluates a named contract; generated cluster
kernels are covered separately (:func:`check_cluster_specs`) because
their specs are synthesized: one whole-array block per operand, which is
trivially divisible but must agree across every member of the cluster.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Callable

import jax.numpy as jnp

from .diagnostics import DiagnosticReport, Severity

if TYPE_CHECKING:
    from repro.compiler.graph import Graph
    from repro.runtime.policies import AnalysisPolicy

#: second-minor / minor tiling the TPU VPU/MXU layouts want (fp32).
_SUBLANE, _LANE = 8, 128


@dataclass(frozen=True)
class TileDim:
    """One tiled dimension of a kernel launch: ``size`` split into
    ``block``-sized programs; ``masked`` means the kernel guards the
    overhang in-kernel, so non-divisible sizes are legal."""

    name: str
    size: int
    block: int
    masked: bool = False


def check_tiling(kernel: str, dims: list[TileDim],
                 vmem_bytes: int | None = None,
                 vmem_limit: int | None = None) -> DiagnosticReport:
    """Divisibility/overhang rules shared by every contract."""
    report = DiagnosticReport()
    for d in dims:
        prov = dict(where=f"{kernel}({d.name}={d.size}, block={d.block})")
        if d.block < 1:
            report.add("tile.empty", Severity.ERROR,
                       f"{d.name}: block size {d.block} < 1", **prov)
            continue
        if d.block > d.size:
            report.add("tile.oversize", Severity.ERROR,
                       f"{d.name}: block {d.block} exceeds extent {d.size}",
                       **prov)
            continue
        if d.size % d.block != 0 and not d.masked:
            last = (d.size // d.block) * d.block
            report.add(
                "tile.oob", Severity.ERROR,
                f"{d.name}: extent {d.size} is not a multiple of block "
                f"{d.block} and the kernel does not mask the overhang — "
                f"the final program reads [{last}:{last + d.block}), "
                f"{last + d.block - d.size} elements out of bounds", **prov)
    if vmem_bytes is not None and vmem_limit is not None \
            and vmem_bytes > vmem_limit:
        report.add("vmem.over-budget", Severity.WARNING,
                   f"per-program VMEM estimate {vmem_bytes} B exceeds the "
                   f"budget {vmem_limit} B", where=kernel)
    return report


# -- declared contracts for the hand-written kernels -------------------------


def _flash_attention(*, b: int, h: int, s: int, d: int, bq: int = 128,
                     bk: int = 128, dtype: Any = jnp.float32,
                     vmem_limit: int | None = None) -> DiagnosticReport:
    bq, bk = min(bq, s), min(bk, s)
    itemsize = jnp.dtype(dtype).itemsize
    # q tile + k tile + v tile + scores + fp32 (m, l, acc) scratch
    vmem = (bq * d + 2 * bk * d) * itemsize \
        + (bq * bk + bq * (d + 2)) * 4
    return check_tiling(
        "flash_attention",
        [TileDim("seq/bq", s, bq), TileDim("seq/bk", s, bk)],
        vmem_bytes=vmem, vmem_limit=vmem_limit)


def _flash_decode(*, n: int, s: int, d: int, bk: int = 512,
                  dtype: Any = jnp.float32,
                  vmem_limit: int | None = None) -> DiagnosticReport:
    bk = min(bk, s)
    itemsize = jnp.dtype(dtype).itemsize
    vmem = (d + 2 * bk * d) * itemsize + (bk + d + 2) * 4
    # the validity mask handles cache-depth raggedness *within* the
    # grid, but the grid itself must cover the cache exactly
    return check_tiling("flash_decode", [TileDim("cache/bk", s, bk)],
                        vmem_bytes=vmem, vmem_limit=vmem_limit)


def _flash_verify(*, n: int, t: int, s: int, d: int, bk: int = 512,
                  dtype: Any = jnp.float32,
                  vmem_limit: int | None = None) -> DiagnosticReport:
    """Wide-verify flash decoding (``kernels/flash_decode.flash_verify``):
    ``flash_decode`` with ``t`` query tokens per row sharing each
    streamed KV tile.  The [t, bk] validity mask handles causal/ragged
    structure within the grid; the grid must cover the cache exactly,
    and the whole t-span (queries + fp32 statistics) is VMEM-resident
    per program."""
    bk = min(bk, s)
    itemsize = jnp.dtype(dtype).itemsize
    # q span + k tile + v tile + [t, bk] mask/scores + fp32 (m, l, acc)
    vmem = (t * d + 2 * bk * d) * itemsize + (t * bk + t * (d + 2)) * 4
    return check_tiling("flash_verify", [TileDim("cache/bk", s, bk)],
                        vmem_bytes=vmem, vmem_limit=vmem_limit)


def _paged_decode(*, h: int, kv: int, d: int, block_size: int,
                  max_blocks: int, pps: int | None = None,
                  dtype: Any = jnp.bfloat16, on_tpu: bool = True,
                  vmem_limit: int | None = None) -> DiagnosticReport:
    """Paged decode read in place (``kernels/paged_decode.paged_decode``):
    one DMA brings a page, a ``[block_size * kv, d]`` tile of the pool
    viewed as ``[num_blocks, block_size * kv, d]``, and ``pps`` pages
    make a step.  On the TPU the page view must be a bitcast of the pool
    and a page whole sublane tiles (``unfit_page_layout``; the engine
    keeps the gather path where it is not); steps past a slot's last
    live page are masked in-kernel, so the table's width need not divide
    into steps."""
    from repro.kernels.paged_decode import pages_per_step, unfit_page_layout

    itemsize = jnp.dtype(dtype).itemsize
    if pps is None:
        pps = pages_per_step(block_size, kv * d * itemsize)
    rows = pps * block_size * kv
    # double-buffered K and V page groups + q/out for one slot, fp32
    # scores and (m, l, acc) statistics
    vmem = (4 * rows * d + 2 * h * d) * itemsize + (h * rows
                                                    + h * (d + 2)) * 4
    report = check_tiling(
        "paged_decode",
        [TileDim("table/pages", max_blocks, min(pps, max_blocks),
                 masked=True)],
        vmem_bytes=vmem, vmem_limit=vmem_limit)
    unfit = on_tpu and unfit_page_layout(block_size, kv, d, dtype)
    if unfit:
        report.add(
            "tile.unaligned", Severity.ERROR,
            f"{unfit}; the gather path serves this pool",
            where=f"paged_decode(block_size={block_size}, kv={kv}, d={d})")
    return report


def _matmul(*, m: int, k: int, n: int, bm: int = 128, bn: int = 128,
            bk: int = 128, dtype: Any = jnp.float32,
            vmem_limit: int | None = None) -> DiagnosticReport:
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    itemsize = jnp.dtype(dtype).itemsize
    vmem = (bm * bk + bk * bn + bm * bn) * itemsize + bm * bn * 4
    report = check_tiling(
        "matmul",
        [TileDim("m", m, bm), TileDim("n", n, bn), TileDim("k", k, bk)],
        vmem_bytes=vmem, vmem_limit=vmem_limit)
    if n % _LANE != 0 or k % _LANE != 0:
        report.add("tile.lane-misaligned", Severity.INFO,
                   f"contraction/minor dims ({k}, {n}) are not multiples "
                   f"of {_LANE}; the MXU pads to full lanes",
                   where="matmul")
    return report


def _rms_norm(*, n: int, d: int, bn: int = 256,
              dtype: Any = jnp.float32,
              vmem_limit: int | None = None) -> DiagnosticReport:
    bn = min(bn, n)
    while n % bn != 0:        # the launch wrapper shrinks bn to divide n
        bn -= 1
    itemsize = jnp.dtype(dtype).itemsize
    vmem = (2 * bn * d + d) * itemsize
    return check_tiling("rms_norm", [TileDim("rows", n, bn)],
                        vmem_bytes=vmem, vmem_limit=vmem_limit)


def _attention_template(*, sq: int, sk: int, d: int, dv: int | None = None,
                        bq: int = 128, bk: int = 128,
                        dtype: Any = jnp.float32,
                        vmem_limit: int | None = None) -> DiagnosticReport:
    """The parameterized attention template the attention matcher lowers
    to (``kernels/flash_attention.attention_template``): like
    ``flash_attention`` but q and kv sequence lengths may differ and the
    template never masks — both grids must divide exactly."""
    dv = d if dv is None else dv
    bq, bk = min(bq, sq), min(bk, sk)
    itemsize = jnp.dtype(dtype).itemsize
    # q tile + k tile + v tile + scores + bias tile + fp32 (m, l, acc)
    vmem = (bq * d + bk * d + bk * dv) * itemsize \
        + (2 * bq * bk + bq * (dv + 2)) * 4
    return check_tiling(
        "attention_template",
        [TileDim("sq/bq", sq, bq), TileDim("sk/bk", sk, bk)],
        vmem_bytes=vmem, vmem_limit=vmem_limit)


def _matmul_epilogue(*, m: int, k: int, n: int, bm: int = 128,
                     bn: int = 128, bk: int = 128, reduce: bool = False,
                     n_extra: int = 0, dtype: Any = jnp.float32,
                     vmem_limit: int | None = None) -> DiagnosticReport:
    """The fused matmul-epilogue kernel (``kernels/matmul.matmul_epilogue``):
    matmul tiling rules, plus — when the epilogue body contains a row
    reduction (``reduce=True``) — the output tile must hold complete rows
    (``bn == n``), or each program reduces over a partial row and the
    softmax/rmsnorm denominator is silently wrong."""
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    itemsize = jnp.dtype(dtype).itemsize
    vmem = (bm * bk + bk * bn + (1 + n_extra) * bm * bn) * itemsize \
        + bm * bn * 4
    report = check_tiling(
        "matmul_epilogue",
        [TileDim("m", m, bm), TileDim("n", n, bn), TileDim("k", k, bk)],
        vmem_bytes=vmem, vmem_limit=vmem_limit)
    if reduce and bn != n:
        report.add(
            "tile.epilogue-row", Severity.ERROR,
            f"epilogue body reduces over rows but the n tile is {bn} < "
            f"{n} — each program sees a partial row, so the reduction "
            "result is wrong (plan_epilogue must force bn == n)",
            where=f"matmul_epilogue(n={n}, bn={bn})")
    return report


def _reduction_cluster(*, shape: tuple[int, ...], n_operands: int = 2,
                       dtype: Any = jnp.float32,
                       vmem_limit: int | None = None) -> DiagnosticReport:
    """A generated reduction-cluster kernel: whole-array blocks (the body
    replays the subgraph on full operands), so divisibility is trivial —
    the contract is that the whole working set is VMEM-resident."""
    itemsize = jnp.dtype(dtype).itemsize
    size = 1
    for s in shape:
        size *= s
    dims = [TileDim(f"axis{i}", s, s) for i, s in enumerate(shape)]
    return check_tiling("reduction_cluster", dims,
                        vmem_bytes=n_operands * size * itemsize,
                        vmem_limit=vmem_limit)


KERNEL_CONTRACTS: dict[str, Callable[..., DiagnosticReport]] = {
    "flash_attention": _flash_attention,
    "flash_decode": _flash_decode,
    "flash_verify": _flash_verify,
    "paged_decode": _paged_decode,
    "matmul": _matmul,
    "rms_norm": _rms_norm,
    "attention_template": _attention_template,
    "matmul_epilogue": _matmul_epilogue,
    "reduction_cluster": _reduction_cluster,
}


def check_kernel_call(kernel: str, **params: Any) -> DiagnosticReport:
    """Evaluate a declared kernel contract against launch parameters.

    ``check_kernel_call("matmul", m=256, k=130, n=256, bk=128)`` →
    ``tile.oob`` (130 % 128 != 0 and nothing masks the overhang).
    """
    try:
        contract = KERNEL_CONTRACTS[kernel]
    except KeyError:
        raise KeyError(f"no declared contract for kernel {kernel!r}; "
                       f"known: {sorted(KERNEL_CONTRACTS)}") from None
    return contract(**params)


# -- generated cluster kernels ----------------------------------------------


def check_cluster_specs(graph: "Graph",
                        policy: "AnalysisPolicy | None" = None,
                        on_tpu: bool = False,
                        where: str | None = None) -> DiagnosticReport:
    """Audit the specs the cluster lowering would generate.

    ``elementwise``/``reduction`` clusters use one whole-array BlockSpec
    per operand, so the only tiling risks are TPU-specific: shape
    disagreement across members and lane/sublane misalignment both force
    the jit fallback there (off-TPU the interpreted whole-array body
    handles any shape mix exactly), so they are INFO provenance notes.
    ``epilogue``/``attention`` clusters carry their own tiled specs whose
    contracts the matcher pre-validated (``plan_epilogue`` /
    ``template_supported``); their launch parameters are covered by the
    named :data:`KERNEL_CONTRACTS` instead.
    """
    from repro.runtime.policies import AnalysisPolicy

    policy = policy or AnalysisPolicy()
    report = DiagnosticReport()
    if not policy.enabled:
        return report
    for cl in graph.clusters:
        if cl.kind in ("epilogue", "attention"):
            continue
        if not on_tpu:
            continue
        nodes = [graph.nodes[u] for u in cl.node_ids if u in graph.nodes]
        ins = [graph.nodes[u] for u in cl.inputs if u in graph.nodes]
        shapes = {tuple(n.shape) for n in nodes} | {tuple(n.shape)
                                                    for n in ins}
        if len(shapes) > 1:
            # TPU lowering falls back to jit for these; only a
            # hand-forced pallas path would be OOB, so INFO provenance
            report.add("tile.shape-divergent", Severity.INFO,
                       f"cluster spans shapes {sorted(shapes)}; pallas "
                       "path unavailable on TPU (jit fallback)",
                       cluster=cl.cid, where=where)
            continue
        if not shapes:
            continue
        (shape,) = shapes
        if len(shape) < 2 or shape[-1] % _LANE or shape[-2] % _SUBLANE:
            report.add("tile.unaligned", Severity.INFO,
                       f"cluster shape {shape} is not ({_SUBLANE}k, "
                       f"{_LANE}k)-tileable on TPU; jit fallback",
                       cluster=cl.cid, where=where)
    return report


def estimate_grid(size: int, block: int) -> int:
    """Programs needed to cover ``size`` with ``block`` (helper for
    contracts and tests)."""
    return math.ceil(size / block)
