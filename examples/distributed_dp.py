"""Explicit-SPMD data-parallel training via the DistributedInterface
(paper §4.1.3 / A.4.1): shard_map training step with bucketed, optionally
int8-compressed (error-feedback) gradient all-reduce.

Runs in one process over the default backend's devices.  Before JAX is
imported it asks for 8 host (CPU) devices, so a CPU-only run still has an
8-way data axis; on an accelerator host the accelerator's devices are the
default and the host devices go unused.

Run:  PYTHONPATH=src python examples/distributed_dp.py
"""

import os

HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=8"


def _worker():
    import jax

    import repro
    from repro.core.distributed import ShardMapBackend, init_distributed
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()),), ("data",))
    dist = init_distributed(ShardMapBackend("data"))

    with repro.session(mesh=mesh, batch_axes=("data",),
                       tag="distributed_dp"):
        _train(mesh, dist)


def _train(mesh, dist):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import GradientSynchronizer, GradSyncConfig

    d, classes = 32, 4
    k = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(k, (d, classes)) * 0.1,
              "b": jnp.zeros((classes,))}

    rng = np.random.default_rng(0)
    centers = rng.standard_normal((classes, d)) * 2
    ys = rng.integers(0, classes, 1024)
    xs = (centers[ys] + rng.standard_normal((1024, d))).astype(np.float32)

    for compress in ("none", "int8"):
        sync = GradientSynchronizer(dist, GradSyncConfig(compress=compress))

        def local_loss(p, x, y):
            logits = x @ p["w"] + p["b"]
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), y[:, None], 1))

        def step(p, ef, x, y):
            # per-shard gradient, then interface-level all-reduce
            loss, grads = jax.value_and_grad(local_loss)(p, x, y)
            grads, ef = sync(grads, ef)
            new_p = jax.tree.map(lambda w, g: w - 0.5 * g, p, grads)
            return new_p, ef, jax.lax.pmean(loss, "data")

        ef0 = sync.init_state(params)
        sharded_step = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: P(), ef0), P("data"),
                      P("data")),
            out_specs=(P(), jax.tree.map(lambda _: P(), ef0), P()),
            check_vma=False))

        p, ef = params, ef0
        losses = []
        for i in range(30):
            x = jnp.asarray(xs[(i * 256) % 768:(i * 256) % 768 + 256])
            y = jnp.asarray(ys[(i * 256) % 768:(i * 256) % 768 + 256])
            p, ef, loss = sharded_step(p, ef, x, y)
            losses.append(float(loss))
        print(f"[distributed_dp] compress={compress:5s} "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f} on "
              f"{dist.__class__.__name__} world={len(jax.devices())}")
        assert losses[-1] < losses[0] * 0.5
    print("distributed_dp OK")


def main():
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {HOST_DEVICES_FLAG}".strip()
    _worker()


if __name__ == "__main__":
    main()
