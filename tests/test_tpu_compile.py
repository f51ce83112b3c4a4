"""Main-path kernels compiled for a described TPU v5e, at starcoder2-7b widths.

Nothing runs: each test asks the TPU compiler, which works without a chip
attached, to build one kernel for ``v5e:2x2`` device 0, so a kernel the
chip would refuse (an illegal block shape, a primitive Mosaic cannot
lower, more VMEM than a core has) fails here.  The topology is described
inside a fixture, never at import: only one process may load the TPU
library, and collection happens in every test worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.compiler import CompilerPolicy, compile_graph, trace
from repro.compiler.lowering import ClusterStep
from repro.core.tensor import ops
from repro.core.tensor.lazy_backend import LazyBackend
from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import matmul as mm
from repro.kernels import paged_decode as pd
from repro.kernels import rmsnorm

# starcoder2-7b: d_model 4608, 36 heads / 4 KV heads of 128, d_ff 18432
D_MODEL, HEAD_DIM, D_FF = 4608, 128, 18432


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def struct(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_steps(fn, struct, *arrays):
    """Trace ``fn`` through ``repro.compile``'s pipeline and lower it for
    the TPU (not the interpreter); returns each Pallas cluster step with
    the shapes of its inputs."""
    lb = LazyBackend()
    with repro.session(backend=lb):
        out = fn(*[lb._lift(a) for a in arrays])
    graph, _ = trace([out])
    exe = compile_graph(graph, CompilerPolicy(), interpret=False)
    return [(s, [struct(graph.nodes[u].shape, graph.nodes[u].dtype)
                 for u in s.inputs])
            for s in exe.steps
            if isinstance(s, ClusterStep) and s.kind == "pallas"]


def test_flash_attention_compiles(struct):
    x = struct((1, 8, 2048, HEAD_DIM), jnp.bfloat16)
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
             x, x, x)


@pytest.mark.parametrize("mask_shape", [(64, 4096), (4096,)],
                         ids=["per_row", "shared"])
def test_flash_decode_compiles(struct, mask_shape):
    q = struct((64, HEAD_DIM), jnp.bfloat16)
    kv = struct((64, 4096, HEAD_DIM), jnp.bfloat16)
    _compile(fd.flash_decode, q, kv, kv, struct(mask_shape, jnp.bool_))


def test_flash_verify_compiles(struct):
    kv = struct((64, 4096, HEAD_DIM), jnp.bfloat16)
    _compile(fd.flash_verify, struct((64, 5, HEAD_DIM), jnp.bfloat16), kv, kv,
             struct((64, 5, 4096), jnp.bool_))


#: KV heads, head dim -> whether the page view is a bitcast of the pool
PAGED_POOLS = {(1, 128): True, (2, 128): True, (4, 128): True,
               (8, 128): True, (8, 256): True, (4, 256): False}


@pytest.mark.parametrize("kv,d", PAGED_POOLS,
                         ids=[f"kv{kv}_d{d}" for kv, d in PAGED_POOLS])
def test_paged_decode_compiles(struct, kv, d):
    """16 slots in blocks of 16, nine query heads a KV head as
    starcoder2-7b's: the pool's ``[num_blocks, block_size * kv, d]``
    view is a bitcast (no temporary) exactly where
    ``unfit_page_layout`` lets the engine pick the kernel, and a copy of
    the pool where it does not.  The pools are 512 MiB, too large for
    the compiler to hide a copy in VMEM."""
    blocks = (512 << 20) // (16 * kv * d * 2)
    pool = struct((blocks * 16, kv, d), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, t, n: pd.paged_decode(q, k, v, t, n, block_size=16,
                                              scale=d ** -0.5),
        struct((16, 9 * kv, d), jnp.bfloat16), pool, pool,
        struct((16, 256), jnp.int32), struct((16,), jnp.int32))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert (temp < 1 << 20) == PAGED_POOLS[kv, d], temp
    assert (pd.unfit_page_layout(16, kv, d, jnp.bfloat16) is None) \
        == PAGED_POOLS[kv, d]


def test_matmul_compiles(struct):
    _compile(mm.matmul, struct((2048, D_MODEL), jnp.bfloat16),
             struct((D_MODEL, D_FF), jnp.bfloat16))


def test_matmul_epilogue_compiles(struct):
    """``gelu(x @ w + b)`` as the compiler lowers it: one epilogue kernel
    with a rank-1 bias and an exact (erf) gelu in its body."""
    def f(x, w, b):
        return ops.gelu(ops.add(ops.matmul(x, w), b))

    ((step, args),) = _kernel_steps(
        f, struct, jnp.zeros((2048, D_MODEL), jnp.bfloat16),
        jnp.zeros((D_MODEL, D_FF), jnp.bfloat16),
        jnp.zeros((D_FF,), jnp.bfloat16))
    assert step.cluster_kind == "epilogue"
    _compile(step.fn, *args)


def test_rms_norm_compiles(struct):
    _compile(rmsnorm.rms_norm, struct((2048, D_MODEL), jnp.bfloat16),
             struct((D_MODEL,), jnp.float32))


def test_gridded_cluster_kernel_compiles(struct):
    """A 16-op elementwise chain over 4096 x 4608 f32 (75 MB per operand,
    far over VMEM) streams row blocks through one generated kernel."""
    def chain(x):
        for _ in range(4):
            x = ops.tanh(ops.add(ops.mul(x, x), ops.sin(x)))
        return x

    shape = (4096, D_MODEL)
    ((step, args),) = _kernel_steps(chain, struct,
                                    jnp.zeros(shape, jnp.float32))
    assert step.cluster_kind == "elementwise" and step.n_ops == 16
    _compile(step.fn, *args)
