"""Generated cluster kernels for the compiler's fused regions.

The compiler's fusion/matcher passes partition a traced graph into
clusters of four kinds; this module synthesizes or dispatches one kernel
per cluster:

* ``elementwise`` / ``reduction`` — the body is generated from the
  cluster's op list (:func:`make_body`), reading every external input once
  from VMEM, running the region's ops on register values, and writing each
  external output once.  That is the ArrayFire-JIT payoff (paper §4.1.1,
  Fig. 2) made concrete: N dispatches collapse into a single kernel whose
  arithmetic intensity grows with the cluster.  A purely elementwise
  cluster over one common shape streams row blocks through a 1-D grid
  sized to the VMEM budget (:func:`row_block`), so it runs at any array
  size.  Reduction-tailed regions (softmax denominators, mean chains)
  keep one whole-array step — the body replays the ops' own closures, so
  mixed shapes are exact — which only the interpreter runs; on TPU they
  take the jit fallback.
* ``epilogue`` — a 2-D matmul plus its consumer cone; the synthesized
  epilogue body is folded into the tiled matmul kernel's store step
  (:func:`repro.kernels.matmul.matmul_epilogue`).
* ``attention`` — an ``act(scale·(q@kᵀ) + bias) @ v`` match; lowered to
  the parameterized flash-attention template
  (:func:`repro.kernels.flash_attention.attention_template`).

Off-TPU the kernels run under ``interpret=True`` (reference semantics);
shapes/dtypes the TPU lowering cannot tile fall back to a per-cluster
``jax.jit`` of the same synthesized body — fusion is an optimization,
never a correctness constraint.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: dtypes the TPU tiling supports for generated elementwise bodies.
_TPU_DTYPES = (jnp.float32, jnp.bfloat16)


def _erf_rational(x):
    """erf by Eigen's single-precision rational approximation (odd
    degree-13 numerator over even degree-8 denominator, input clamped to
    [-4, 4] where erf is +-1 in f32).  Mosaic has no lowering for
    ``lax.erf``; this one uses only multiply, add and divide."""
    alpha = (-2.72614225801306e-10, 2.77068142495902e-08,
             -2.10102402082508e-06, -5.69250639462346e-05,
             -7.34990630326855e-04, -2.95459980854025e-03,
             -1.60960333262415e-02)
    beta = (-1.45660718464996e-05, -2.13374055278905e-04,
            -1.68282697438203e-03, -7.37332916720468e-03,
            -1.42647390514189e-02)
    x32 = jnp.clip(x.astype(jnp.float32), -4.0, 4.0)
    x2 = x32 * x32
    p = jnp.full_like(x2, alpha[0])
    for a in alpha[1:]:
        p = p * x2 + a
    q = jnp.full_like(x2, beta[0])
    for b in beta[1:]:
        q = q * x2 + b
    return (x32 * p / q).astype(x.dtype)


def make_body(nodes: Sequence[Any], input_ids: Sequence[int],
              output_ids: Sequence[int], mosaic: bool = False) -> Callable:
    """Synthesize the cluster's straight-line body: values in, values out.

    Shared by every lowering: the Pallas kernel wraps it with ref
    reads/writes; the jit fallback compiles it directly.  ``mosaic``
    marks a body compiled to a TPU kernel, where ``erf`` becomes
    :func:`_erf_rational`.
    """
    nodes = tuple(nodes)
    input_ids = tuple(input_ids)
    output_ids = tuple(output_ids)
    fns = [_erf_rational if mosaic and n.op == "erf" else n.fn
           for n in nodes]

    def body(*vals):
        env = dict(zip(input_ids, vals))
        for n, fn in zip(nodes, fns):
            env[n.uid] = fn(*[env[d] for d in n.inputs])
        return tuple(env[o] for o in output_ids)

    body.__name__ = f"cluster_{'_'.join(n.op for n in nodes[:4])}"
    return body


def row_block(nodes: Sequence[Any], input_nodes: Sequence[Any],
              n_outputs: int, vmem_limit: int) -> int | None:
    """Rows per grid step for a row-gridded cluster kernel, or None.

    Applies to clusters of rank-agnostic elementwise ops over one common
    shape of rank >= 2: the kernel views every operand as ``[rows, cols]``
    (leading dims flattened) and streams ``(block, cols)`` tiles.  The
    block is the largest divisor of ``rows`` that is a multiple of 8 (or
    ``rows`` itself) whose double-buffered tiles of every input and output
    fit ``vmem_limit``; None when the cluster is not of that form or not
    even 8 rows fit.
    """
    from repro.compiler.graph import ELEMENTWISE_OPS

    shapes = {tuple(n.shape) for n in list(nodes) + list(input_nodes)}
    if len(shapes) != 1 or any(n.op not in ELEMENTWISE_OPS for n in nodes):
        return None
    (shape,) = shapes
    if len(shape) < 2:
        return None
    rows, cols = math.prod(shape[:-1]), shape[-1]
    itemsize = max(jnp.dtype(n.dtype).itemsize
                   for n in list(nodes) + list(input_nodes))
    fit = vmem_limit // (2 * (len(input_nodes) + n_outputs) * cols * itemsize)
    if rows <= fit:
        return rows
    for block in range(fit - fit % 8, 7, -8):
        if rows % block == 0:
            return block
    return None


def pallas_supported(nodes: Sequence[Any], input_nodes: Sequence[Any],
                     n_outputs: int, on_tpu: bool, vmem_limit: int) -> bool:
    """Can this elementwise/reduction cluster become one ``pallas_call``?

    Off-TPU the kernel runs under interpret mode, which is exact for any
    mix of shapes (implicit broadcasting, keepdims/keepdims-less
    reductions); only rank-0 values are kept on the jit path.  On TPU the
    cluster must be row-griddable (:func:`row_block`) over a VPU-tileable
    shape in supported dtypes; everything else, reduction tails included,
    takes the counted jit fallback.
    """
    shapes = {tuple(n.shape) for n in nodes}
    shapes |= {tuple(n.shape) for n in input_nodes}
    if any(len(s) == 0 for s in shapes):
        return False
    if not on_tpu:
        return True
    if row_block(nodes, input_nodes, n_outputs, vmem_limit) is None:
        return False
    (shape,) = shapes
    if shape[-1] % 128 != 0 or shape[-2] % 8 != 0:
        return False
    dtypes = {jnp.dtype(n.dtype) for n in list(nodes) + list(input_nodes)}
    return all(d in _TPU_DTYPES for d in dtypes)


def build_cluster_kernel(nodes: Sequence[Any], input_nodes: Sequence[Any],
                         output_nodes: Sequence[Any], vmem_limit: int,
                         interpret: bool = True) -> Callable:
    """One ``pallas_call`` for the whole cluster.

    Returns ``fn(*input_arrays) -> tuple(output_arrays)``; the kernel body
    is generated from the cluster's op list (see :func:`make_body`).  It
    is row-gridded where :func:`row_block` finds a block, and one
    whole-array step otherwise.
    """
    body = make_body(nodes, [n.uid for n in input_nodes],
                     [n.uid for n in output_nodes], mosaic=not interpret)
    n_in = len(input_nodes)

    def kernel(*refs):
        ins = [r[...] for r in refs[:n_in]]
        outs = body(*ins)
        for r, v in zip(refs[n_in:], outs):
            r[...] = v

    block = row_block(nodes, input_nodes, len(output_nodes), vmem_limit)
    if block is None:
        out_shape = [jax.ShapeDtypeStruct(tuple(n.shape), n.dtype)
                     for n in output_nodes]
        call = pl.pallas_call(kernel, out_shape=out_shape,
                              interpret=interpret)

        def run(*vals):
            return tuple(call(*vals))
    else:
        shape = tuple(output_nodes[0].shape)
        cols = shape[-1]
        rows = math.prod(shape[:-1])
        spec = pl.BlockSpec((block, cols), lambda i: (i, 0))
        call = pl.pallas_call(
            kernel, grid=(rows // block,), in_specs=[spec] * n_in,
            out_specs=[spec] * len(output_nodes),
            out_shape=[jax.ShapeDtypeStruct((rows, cols), n.dtype)
                       for n in output_nodes],
            interpret=interpret)

        def run(*vals):
            outs = call(*[v.reshape(rows, cols) for v in vals])
            return tuple(o.reshape(shape) for o in outs)

    run.__name__ = f"pallas_{body.__name__}"
    return run


def build_jit_cluster(nodes: Sequence[Any], input_nodes: Sequence[Any],
                      output_nodes: Sequence[Any]) -> Callable:
    """Per-cluster ``jax.jit`` fallback over the same synthesized body."""
    body = make_body(nodes, [n.uid for n in input_nodes],
                     [n.uid for n in output_nodes])
    return jax.jit(body)


# -- attention clusters ------------------------------------------------------


def attention_supported(input_nodes: Sequence[Any], meta: dict,
                        on_tpu: bool) -> bool:
    """Does the matched attention cluster satisfy the template's tile
    contract?  Off-TPU (interpret) the template takes any match; on TPU
    every dimension must be lane/MXU aligned — otherwise lowering falls
    back to a per-cluster ``jax.jit`` of the cluster body."""
    from repro.kernels.flash_attention import template_supported

    by_uid = {n.uid: n for n in input_nodes}
    q = by_uid.get(meta["q"])
    k = by_uid.get(meta["k"])
    v = by_uid.get(meta["v"])
    if q is None or k is None or v is None:
        return False
    sq, d = q.shape[-2], q.shape[-1]
    sk = k.shape[-2] if meta["k_layout"] == "std" else k.shape[-1]
    dv = v.shape[-1]
    dtypes = [q.dtype, k.dtype, v.dtype]
    return template_supported(sq=sq, sk=sk, d=d, dv=dv, dtypes=dtypes,
                              on_tpu=on_tpu)


def build_attention_cluster(input_nodes: Sequence[Any],
                            output_nodes: Sequence[Any], meta: dict,
                            interpret: bool = True) -> Callable:
    """Lower a matched attention cluster to the flash-style template.

    Maps the cluster's positional inputs to their matched roles (q/k/v and
    the optional additive bias), flattens leading batch dims, and calls
    :func:`repro.kernels.flash_attention.attention_template`.  Unused
    cluster inputs (the uniform consts the matcher peeled — scales, the
    sigmoid ones) are accepted positionally and ignored.
    """
    from repro.kernels import flash_attention as fa

    pos = {n.uid: i for i, n in enumerate(input_nodes)}
    by_uid = {n.uid: n for n in input_nodes}
    q_i, k_i, v_i = pos[meta["q"]], pos[meta["k"]], pos[meta["v"]]
    bias_uid = meta["bias"]
    b_i = pos[bias_uid] if bias_uid is not None else None

    q_shape = tuple(by_uid[meta["q"]].shape)
    k_shape = tuple(by_uid[meta["k"]].shape)
    v_shape = tuple(by_uid[meta["v"]].shape)
    lead = q_shape[:-2]
    n_batch = math.prod(lead) if lead else 1
    sq, d = q_shape[-2], q_shape[-1]
    sk = k_shape[-2] if meta["k_layout"] == "std" else k_shape[-1]
    dv = v_shape[-1]
    out_node = output_nodes[0]
    out_shape, out_dtype = tuple(out_node.shape), out_node.dtype

    bias_spec = "none"
    if bias_uid is not None:
        bshape = tuple(by_uid[bias_uid].shape)
        bias_spec = ("3d" if len(bshape) > 2
                     and any(x != 1 for x in bshape[:-2]) else "2d")

    bq = 128 if sq % 128 == 0 else sq
    bk = 128 if sk % 128 == 0 else sk
    mode, scale = meta["mode"], float(meta["scale"])
    bias_scale, k_layout = float(meta["bias_scale"]), meta["k_layout"]

    def run_impl(*vals):
        q3 = vals[q_i].reshape((n_batch, sq, d))
        if k_layout == "std":
            k3 = vals[k_i].reshape((n_batch, sk, d))
        else:
            k3 = vals[k_i].reshape((n_batch, d, sk))
        v3 = vals[v_i].reshape((n_batch, sk, dv))
        bias = None
        if b_i is not None:
            if bias_spec == "3d":
                bias = jnp.broadcast_to(
                    vals[b_i], lead + (sq, sk)).reshape(n_batch, sq, sk)
            else:
                bias = jnp.broadcast_to(vals[b_i], (sq, sk))
        out = fa.attention_template(
            q3, k3, v3, bias, mode=mode, scale=scale,
            bias_scale=bias_scale, k_layout=k_layout, bias_spec=bias_spec,
            bq=bq, bk=bk, interpret=interpret)
        return (out.reshape(out_shape).astype(out_dtype),)

    run = jax.jit(run_impl)
    return run


# -- epilogue clusters -------------------------------------------------------


def build_epilogue_cluster(nodes: Sequence[Any], input_nodes: Sequence[Any],
                           output_nodes: Sequence[Any], meta: dict,
                           interpret: bool = True) -> Callable:
    """Lower an epilogue cluster: the matmul member runs on the tiled MXU
    kernel, and the synthesized epilogue body executes on each output tile
    at the final K step (:func:`repro.kernels.matmul.matmul_epilogue`)."""
    from repro.kernels import matmul as mm_mod

    mm_uid = meta["matmul"]
    epi_members = [n for n in nodes if n.uid != mm_uid]
    body = make_body(epi_members, [mm_uid, *meta["epi_ext"]],
                     [output_nodes[0].uid], mosaic=not interpret)
    pos = {n.uid: i for i, n in enumerate(input_nodes)}
    by_uid = {n.uid: n for n in input_nodes}
    lhs_i, rhs_i = pos[meta["lhs"]], pos[meta["rhs"]]
    extra_is = [pos[u] for u in meta["epi_ext"]]
    extra_shapes = [tuple(by_uid[u].shape) for u in meta["epi_ext"]]

    lhs_n, rhs_n = by_uid[meta["lhs"]], by_uid[meta["rhs"]]
    m, k = lhs_n.shape
    n = rhs_n.shape[1]
    mm_dtype = jnp.promote_types(lhs_n.dtype, rhs_n.dtype)
    out_node = output_nodes[0]

    call = mm_mod.matmul_epilogue(
        body, m=m, k=k, n=n, extra_shapes=extra_shapes,
        out_dtype=out_node.dtype, mm_dtype=mm_dtype,
        bm=meta["bm"], bn=meta["bn"], bk=meta["bk"], interpret=interpret)

    def run(*vals):
        out = call(vals[lhs_i], vals[rhs_i],
                   *[vals[i] for i in extra_is])
        return (out,)

    run.__name__ = "pallas_epilogue_matmul"
    return run
