"""Training cells: the launch path's jitted train step (``train_loop``'s
step function with AdamW, parameters and optimizer state placed by the
sharding rules and donated) driven back to back on packed batches.

Set-up builds the seeded weights on the device in one jitted call,
builds the step once, and drives that same step through its first
``check_steps`` steps, reading what the check needs: each step's loss,
the first gradient as the optimizer got it (its first moment after one
step over ``1 - b1``), and the parameters' change after the last of
them.  The window then goes on with the same step and state.  After the
window the state is freed and the float32 reference follows the same
first steps.
"""

from __future__ import annotations

import numpy as np

from bench.lib import check, harness, stats, traffic, weights
from bench.lib.harness import clock


def _names(params) -> list[str]:
    import jax

    return [weights.program_name(path)[0] for path, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]]


def _leaf_norms(leaves) -> list:
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in leaves]


def _leaf(name: str, prog: dict, ref: dict, key: str) -> list:
    """[leaf, program's norm, reference's norm] of the worst leaf."""
    return [name, prog[key][name], ref[key][name]]


def run(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import repro
    from repro.core.optim import AdamW
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.sharding.rules import make_rules
    from repro.training import train_loop

    t = run.traffic
    hp = t["optimizer"]
    cfg = harness.program_config(run.config, run.ref)
    model = build_model(cfg)
    mesh = make_host_mesh()
    rules = make_rules(t["sharding"])
    optimizer = AdamW(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                      weight_decay=hp["weight_decay"])
    p_sh = rules.tree_shardings(model.abstract_params(), mesh)
    o_sh = jax.tree.map(optimizer.state_sharding_like, p_sh,
                        is_leaf=lambda x: isinstance(x, NamedSharding))
    lay = run.ref.layout(run.config)
    make_params = jax.jit(lambda k: weights.program_tree(
        model.param_structs(), lay, k), out_shardings=p_sh)
    key = weights.seed_key(run.seed)
    params = make_params(key)
    names = _names(params)
    tcfg = train_loop.TrainConfig(
        steps=hp["schedule_steps"], base_lr=hp["lr"], warmup=hp["warmup"],
        grad_clip=hp["grad_clip"])
    tokens_per_step = t["batch"] * t["seq"]

    def feed(i):
        with run.annotate("bench.next_batch"):
            tok, lab = traffic.packed_rows(t, run.seed, cfg.vocab_size, i)
            return {"tokens": jnp.asarray(tok), "labels": jnp.asarray(lab)}

    with repro.session(mesh=mesh, sharding_rules=rules,
                       tag=f"bench:{run.workload['name']}"):
        step_fn = jax.jit(train_loop.make_step_fn(model, optimizer, tcfg),
                          donate_argnums=(0, 1),
                          out_shardings=(p_sh, o_sh, None))
        opt_state = optimizer.init(params)
        norms = jax.jit(_leaf_norms)
        state = {"params": params, "opt": opt_state}
        del params, opt_state

        def step(i) -> float:
            batch = feed(i)
            with run.annotate("bench.train_step"):
                state["params"], state["opt"], metrics = step_fn(
                    state["params"], state["opt"], jnp.int32(i), batch)
                return float(metrics["loss"])

        n_check = t["check_steps"]
        losses, first = [], None
        for i in range(n_check):
            losses.append(step(i))
            if i == 0:
                treedef = jax.tree.structure(state["params"])
                moments = [s["m"] for s in
                           treedef.flatten_up_to(state["opt"])]
                first = [float(x) / (1.0 - hp["b1"])
                         for x in norms(moments)]
        start = make_params(key)
        change = [float(x) for x in norms(jax.tree.leaves(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            state["params"], start)))]
        harness.free(start)
        del start
        prog = {"losses": losses, "grad_norms": dict(zip(names, first)),
                "change_norms": dict(zip(names, change))}

        counter = harness.CompileCounter()
        if run.trace:
            harness.start_trace(run)
        counter.armed = True
        ends, window_losses = [], []
        i = n_check
        t0 = clock()
        t1 = t0 + run.seconds
        with run.annotate("bench.window"):
            while True:
                loss = step(i)
                end = clock()
                i += 1
                if end > t1:
                    break
                ends.append(end)
                window_losses.append(loss)
        counter.close()
        if run.trace:
            harness.stop_trace()

    peak = harness.peak_bytes(jax.devices()[:run.chips])
    harness.free(state)
    state.clear()
    tok_s, steps = stats.train_window(ends, t0, t1, tokens_per_step)
    counts = run.ref.counts(run.config)
    flops_per_token = counts.train_flops_per_token(t["seq"])
    counters = {"steps": steps, "tokens": steps * tokens_per_step,
                "window_s": max(ends) - t0,
                "model_flops": flops_per_token * steps * tokens_per_step,
                "losses": window_losses}
    batches = [traffic.packed_rows(t, run.seed, cfg.vocab_size, i)
               for i in range(n_check)]
    ref = run.ref.train_reference(run.config, run.seed, batches, hp)
    numbers = check.train_numbers(prog, ref)
    correct, checks = check.judge(numbers, run.limits())
    failed = sum(1 for x in window_losses if not np.isfinite(x))
    return {"t0": t0, "t1": t1, "e2e": {"train_tok_s": tok_s},
            "counters": counters, "correct": correct and failed == 0,
            "checks": checks,
            "check_info": {"loss_gap": numbers["loss_gap"],
                           "grad_gap": numbers["grad_gap"],
                           "grad_leaf": _leaf(numbers["grad_leaf"],
                                              prog, ref, "grad_norms"),
                           "change_leaf": _leaf(numbers["change_leaf"],
                                                prog, ref, "change_norms"),
                           "still_leaves": numbers["still_leaves"],
                           "losses": losses, "ref_losses": ref["losses"]},
            "attempted": steps, "failed": failed,
            "memory_peak_bytes": peak,
            "compiles_in_window": counter.count,
            "program_readings": prog, "reference_readings": ref}
