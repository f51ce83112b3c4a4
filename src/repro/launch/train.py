"""End-to-end training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-27b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt

Training runs data-parallel over every device present (the host mesh), or
over the production pod mesh with ``--production-mesh``.  ``--reduced``
runs the same code path end-to-end on the family's smoke-size config.
:func:`run` is the launcher without argument parsing, for callers in the
same process.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

import repro
from repro.configs.base import ModelConfig, get_config
from repro.core.data import (BatchDataset, PackedLMDataset, PrefetchDataset,
                             ShuffleDataset, synthetic_corpus)
from repro.core.optim import AdamW
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.sharding.rules import make_rules
from repro.training.train_loop import TrainConfig, train


def make_batches(cfg, batch_size: int, seq: int, steps: int, seed: int = 0):
    """Data pipeline: synthetic corpus -> packed tokens -> shuffled batches
    -> background prefetch; token ids are folded into the model vocab."""
    docs = synthetic_corpus(n_docs=512, seed=seed)
    ds = PackedLMDataset(docs, seq_len=seq)
    ds = ShuffleDataset(ds, seed=seed)
    batched = PrefetchDataset(BatchDataset(ds, batch_size), buffer_size=4)
    epoch = 0
    produced = 0
    while produced < steps:
        for tokens, labels in batched:
            tokens = np.asarray(tokens) % cfg.vocab_size
            labels = np.asarray(labels) % cfg.vocab_size
            batch = {"tokens": jnp.asarray(tokens),
                     "labels": jnp.asarray(labels)}
            if cfg.family == "encdec":
                rng = np.random.default_rng(produced)
                batch["frames"] = jnp.asarray(
                    rng.standard_normal(
                        (tokens.shape[0], seq // 2, cfg.d_model)),
                    dtype=cfg.compute_dtype)
                batch["tokens"] = batch["tokens"][:, : seq // 2]
                batch["labels"] = batch["labels"][:, : seq // 2]
            elif cfg.family == "vlm":
                rng = np.random.default_rng(produced)
                batch["image_embeds"] = jnp.asarray(
                    rng.standard_normal(
                        (tokens.shape[0], cfg.num_image_tokens,
                         cfg.d_model)),
                    dtype=cfg.compute_dtype)
            yield batch
            produced += 1
            if produced >= steps:
                return
        epoch += 1
        ds.reshuffle(epoch)


def run(cfg: ModelConfig, *, steps: int = 100, batch: int = 8,
        seq: int = 128, lr: float = 1e-3, ckpt: str | None = None,
        production_mesh: bool = False, seed: int = 0) -> list[dict]:
    """Train ``cfg`` from seeded random weights on the synthetic corpus;
    returns the per-step history (``step``, ``loss``, ``sec``)."""
    model = build_model(cfg)
    mesh = make_production_mesh() if production_mesh else make_host_mesh()
    print(f"[train] arch={cfg.name} devices={len(jax.devices())} "
          f"mesh={dict(mesh.shape)}")

    rules = make_rules("baseline")
    optimizer = AdamW(lr=lr)
    # params and optimizer state enter the step placed as it returns them,
    # so step 1 reuses the program step 0 compiled
    p_sh = rules.tree_shardings(model.abstract_params(), mesh)
    o_sh = jax.tree.map(optimizer.state_sharding_like, p_sh,
                        is_leaf=lambda x: isinstance(x, NamedSharding))
    params = jax.jit(model.init, out_shardings=p_sh)(
        jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[train] params: {n_params/1e6:.2f}M")

    tcfg = TrainConfig(steps=steps, base_lr=lr, checkpoint_dir=ckpt,
                       warmup=max(2, steps // 20))
    batches = make_batches(cfg, batch, seq, steps, seed=seed)
    with repro.session(mesh=mesh, sharding_rules=rules,
                       tag=f"train:{cfg.name}"):
        params, history = train(
            model, params, batches, tcfg, optimizer=optimizer,
            jit_kwargs={"out_shardings": (p_sh, o_sh, None)})
    first = np.mean([h["loss"] for h in history[:5]])
    last = np.mean([h["loss"] for h in history[-5:]])
    print(f"[train] loss {first:.4f} -> {last:.4f} over {len(history)} steps")
    return history


def main(argv: list[str] | None = None) -> list[dict]:
    ap = argparse.ArgumentParser(
        description="Train one architecture data-parallel over every "
                    "device present (or the production pod mesh).")
    ap.add_argument("--arch", default="codeqwen1.5-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--production-mesh", action="store_true",
                    help="the 16x16 pod mesh instead of the host's devices")
    args = ap.parse_args(argv)
    enable_compile_cache()
    return run(get_config(args.arch, reduced=args.reduced),
               steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
               ckpt=args.ckpt, production_mesh=args.production_mesh)


if __name__ == "__main__":
    main()
