"""``correct`` on the CPU at a size a test run holds: the runners drive
the program as a benchmark run does, past the look for a chip, and the
check must pass a sound program and fail each fault a cell can have and
the control (the reference in the next lower precision).

Faults are planted in the program under test by patching it for the one
test: a token altered where the decode step produces it; a train step
that returns its state unchanged; a train step that leaves out half the
batch and takes the mean over the rest.  The exchange between chips has
no fault to plant here: every cell runs on one chip.
"""

import json
import time

import jax.numpy as jnp
import pytest

from bench.lib import check, harness, registry, traffic

ROOT = registry.ROOT
TESTDATA = ROOT / "bench" / "testdata"

CHAT = {"runner": "serve", "loop": "open", "rate_per_s": 15.0,
        "layout_seed": 1, "lead_in_s": 0.5,
        "system_prompts": {"count": 3, "min": 40, "max": 100, "zipf_s": 1.0},
        "user_turn": {"median": 20, "sigma": 0.8, "min": 4, "max": 60},
        "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
        "check": {"tokens": 120, "requests": 8}}
DECODE = {"runner": "serve", "loop": "closed", "clients": 4,
          "requests_per_client": 40, "layout_seed": 2, "lead_in_s": 0.3,
          "prompt": {"median": 20, "sigma": 0.6, "min": 8, "max": 64},
          "output": {"median": 40, "sigma": 0.5, "min": 8, "max": 96},
          "check": {"tokens": 120, "requests": 4}}
TRAIN = {"runner": "train", "batch": 4, "seq": 64, "sharding": "baseline",
         "layout_seed": 3, "check_steps": 3,
         "documents": {"median": 20, "sigma": 1.0, "min": 4, "max": 200,
                       "zipf_s": 1.1, "eos_id": 0},
         "optimizer": {"lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                       "weight_decay": 0.1, "warmup": 2,
                       "schedule_steps": 10000, "min_ratio": 0.1,
                       "grad_clip": 1.0}}


def _run(config_name, t, seed=2 ** 31 + 11, seconds=1.5, control=False):
    config = json.loads((TESTDATA / f"{config_name}.json").read_text())
    run = harness.Run(workload={"name": f"test-{config_name}", "chips": 1},
                      config=config, traffic=t,
                      ref=registry.reference(config["reference"], ROOT),
                      seed=seed, seconds=seconds, trace=False, chips=1,
                      t_process=time.perf_counter(), control=control)
    return run, registry.runner(t["runner"], ROOT).run(run)


# -- serving -------------------------------------------------------------------

@pytest.mark.parametrize("t", [CHAT, DECODE], ids=["open", "closed"])
def test_serving_sound_program_is_correct(t):
    _, res = _run("tiny-decoder", t)
    assert res["correct"], res["checks"]
    assert res["check_info"]["tokens_checked"] >= 60
    assert res["compiles_in_window"] == 0
    assert res["e2e"]["serve_tok_s"] > 0


def test_serving_altered_token_is_not_correct(monkeypatch):
    from repro.serving.engine import ServeEngine

    real = ServeEngine._decode_fn

    def altered(self, *args):
        tok, cache = real(self, *args)
        return (tok + 1) % self.model.cfg.vocab_size, cache

    monkeypatch.setattr(ServeEngine, "_decode_fn", altered)
    _, res = _run("tiny-decoder", CHAT)
    assert not res["correct"]
    assert res["checks"]["max_gap"]["value"] > res["checks"]["max_gap"][
        "limit"]


def test_serving_control_is_not_correct():
    _, res = _run("tiny-decoder", CHAT, control=True)
    limit = res["checks"]["max_gap"]["limit"]
    assert res["checks"]["max_gap"]["value"] <= limit
    assert res["check_info"]["control_max_gap"] > limit


# -- training --------------------------------------------------------------------

def test_training_sound_program_is_correct():
    _, res = _run("tiny-mamba2", TRAIN)
    assert res["correct"], res["checks"]
    assert res["compiles_in_window"] == 0


def _patch_step(monkeypatch, wrap):
    from repro.training import train_loop

    real = train_loop.make_step_fn

    def make(model, optimizer, tcfg):
        return wrap(real(model, optimizer, tcfg))

    monkeypatch.setattr(train_loop, "make_step_fn", make)


def test_training_unchanged_state_is_not_correct(monkeypatch):
    def wrap(step):
        def stale(params, opt_state, i, batch):
            _, _, metrics = step(params, opt_state, i, batch)
            return params, opt_state, metrics
        return stale

    _patch_step(monkeypatch, wrap)
    _, res = _run("tiny-mamba2", TRAIN)
    assert not res["correct"]


def test_training_half_batch_is_not_correct(monkeypatch):
    def wrap(step):
        def half(params, opt_state, i, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt_state, i,
                        {k: v[:n] for k, v in batch.items()})
        return half

    _patch_step(monkeypatch, wrap)
    _, res = _run("tiny-mamba2", TRAIN)
    assert not res["correct"], res["checks"]


def test_training_control_is_not_correct():
    run, res = _run("tiny-mamba2", TRAIN)
    config, t = run.config, run.traffic
    vocab = run.ref.dims(config)["v"]
    batches = [traffic.packed_rows(t, run.seed, vocab, i) for i in range(3)]
    ctl = run.ref.train_reference(config, run.seed, batches, t["optimizer"],
                                  quant="fp8")
    numbers = check.train_numbers(ctl, res["reference_readings"])
    correct, checks = check.judge(numbers, run.limits())
    assert not correct, checks


def test_fp8_rounding_is_coarser_than_bf16():
    from bench.lib import lowp

    x = jnp.linspace(-3.0, 3.0, 1001)
    err8 = float(jnp.max(jnp.abs(lowp.fp8_round(x[None], -1) - x)))
    err16 = float(jnp.max(jnp.abs(x.astype(jnp.bfloat16).astype(
        jnp.float32) - x)))
    assert err8 > 4 * err16
