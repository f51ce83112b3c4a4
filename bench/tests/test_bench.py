"""The benchmark's yardstick on the CPU: statistics, operation counts,
traffic, seeded weights, finding files by name, the trace reduction, and
the entry point's refusals.  Nothing here times anything."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench.lib import flops, peaks, registry, stats, trace, traffic, weights

ROOT = registry.ROOT
TESTDATA = ROOT / "bench" / "testdata"


def _bench():
    return registry.load_benchmark(ROOT)


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


# -- statistics ------------------------------------------------------------

@pytest.mark.parametrize("q", [0, 50, 90, 99, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.default_rng(0).exponential(size=137))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_ttft_counts_from_due_and_censors_unfinished():
    t0, t1 = 10.0, 20.0
    reqs = [(9.0, 9.5),      # due before the window: out
            (11.0, 11.25),   # 0.25 s
            (12.0, None),    # no first token by t1: waited 8 s
            (19.0, 21.0),    # first token after t1: waited 1 s
            (20.0, 20.1)]    # due at t1: out
    assert stats.ttft_from_due(reqs, t0, t1) == [0.25, 8.0, 1.0]


def test_inter_token_gaps_and_tokens_in_window():
    times = [[9.0, 10.5, 11.0], [19.5, 20.5]]
    assert stats.inter_token_gaps(times, 10.0, 20.0) == [1.5, 0.5]
    assert stats.tokens_in_window(times, 10.0, 20.0) == 3


def test_train_window_counts_steps_that_ended_inside():
    ends = [11.0, 12.0, 13.0, 21.0]
    tok_s, steps = stats.train_window(ends, 10.0, 20.0, 100)
    assert steps == 3 and tok_s == pytest.approx(300 / 3.0)
    with pytest.raises(ValueError):
        stats.train_window([21.0], 10.0, 20.0, 100)


# -- operations and bytes ----------------------------------------------------

def test_counts_starcoder2_7b_by_hand():
    ref = registry.reference("gqa_decoder", ROOT)
    c = ref.counts(_config("starcoder2-7b"))
    d, q, kv, f = 4608, 36 * 128, 4 * 128, 18432
    layer = 2 * d * q + 2 * d * kv + 2 * d * f      # 217,055,232
    assert layer == 217_055_232
    assert c.matmul_params == 8 * layer + 49152 * d == 1_962_934_272
    assert c.attn_flops_per_key == 4 * 36 * 128 * 8 == 147_456
    assert c.kv_bytes_per_pos == 8 * 2 * 4 * 128 * 2 == 16_384
    assert c.weight_bytes == (1_962_934_272 + 17 * 2 * d) * 2
    # one decode call of 16 slots over 4096 keys each
    assert c.forward_flops(16, 16 * 4096) == (
        2 * 1_962_934_272 * 16 + 147_456 * 16 * 4096)


def test_counts_mamba2_370m_by_hand():
    ref = registry.reference("mamba2", ROOT)
    c = ref.counts(_config("mamba2-370m"))
    layer = 2 * 1024 * 2048 + 2 * 1024 * 128 + 1024 * 32 + 2048 * 1024
    assert layer == 6_586_368
    assert c.matmul_params == 48 * layer + 50288 * 1024 == 367_640_576
    per_layer_ssd = 257 * (128 + 32 * 64) + 4 * 128 * 32 * 64
    assert c.seq_flops_per_token == 48 * per_layer_ssd == 77_174_784
    assert c.train_flops_per_token(2048) == 3 * (2 * 367_640_576
                                                 + 77_174_784)


def test_causal_keys():
    assert flops.causal_keys(0, 4) == 1 + 2 + 3 + 4
    assert flops.causal_keys(2, 4) == 3 + 4


# -- traffic -------------------------------------------------------------------

def _traffic(name):
    return registry.load_traffic(name, ROOT)


def test_chat_traffic_is_the_same_work_for_every_seed():
    t = _traffic("chat-shared")
    a = traffic.chat_open_loop(t, 3, 49152, 40.0)
    b = traffic.chat_open_loop(t, 3, 49152, 40.0)
    c = traffic.chat_open_loop(t, 2 ** 33 + 5, 49152, 40.0)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    key = lambda rs: sorted((len(r.prompt), r.max_new_tokens) for r in rs)
    assert key(a) == key(c)
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert a[-1].due == pytest.approx(c[-1].due, rel=0.2)
    for r in a + c:
        assert t["output"]["min"] <= r.max_new_tokens <= t["output"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= 4096 - 1
    s = t["system_prompts"]
    for sys_id in {r.meta["system"] for r in a}:
        group = [r.prompt for r in a if r.meta["system"] == sys_id]
        if len(group) < 2:
            continue
        n = 0
        while all(len(g) > n and g[n] == group[0][n] for g in group):
            n += 1
        assert s["min"] <= n <= s["max"] + 1


def test_chat_arrivals_have_the_file_rate():
    t = _traffic("chat-shared")
    reqs = traffic.chat_open_loop(t, 1, 100, 400.0)
    span = reqs[-1].due - reqs[0].due
    assert len(reqs) / span == pytest.approx(t["rate_per_s"], rel=0.05)


def test_closed_loop_traffic_fits_and_repeats():
    t = _traffic("decode-long")
    a = traffic.closed_loop_clients(t, 9, 49152)
    b = traffic.closed_loop_clients(t, 9, 49152)
    assert [[r.prompt for r in q] for q in a] == [[r.prompt for r in q]
                                                  for q in b]
    assert len(a) == t["clients"]
    for q in a:
        assert len(q) == t["requests_per_client"]
        for i, r in enumerate(q):
            assert len(r.prompt) + r.max_new_tokens <= 4096 - 1
            assert r.max_new_tokens >= 1
            if i:
                assert (t["output"]["min"] <= r.max_new_tokens
                        <= t["output"]["max"])
                assert (t["prompt"]["min"] <= len(r.prompt)
                        <= t["prompt"]["max"])
    # the first requests start part-way through their answers
    assert any(q[0].meta["folded"] > 0 for q in a)


def test_packed_rows_repeat_per_step_and_differ_between_steps():
    t = _traffic("train-2k")
    a0, l0 = traffic.packed_rows(t, 5, 50288, 0)
    b0, _ = traffic.packed_rows(t, 5, 50288, 0)
    a1, _ = traffic.packed_rows(t, 5, 50288, 1)
    assert a0.shape == (t["batch"], t["seq"]) and a0.dtype == np.int32
    assert (a0 == b0).all() and not (a0 == a1).all()
    assert (a0[:, 1:] == l0[:, :-1]).all()
    assert 0 <= a0.min() and a0.max() < 50288
    rows = {r.tobytes() for r in np.concatenate([a0, a1])}
    assert len(rows) == 2 * t["batch"]


def test_stratified_lengths_are_clipped_quantiles():
    v = traffic.stratified_lognormal(101, 96, 0.8, 16, 512)
    assert v[50] == 96 and v.min() >= 16 and v.max() <= 512
    assert (np.diff(v) >= 0).all()
    assert traffic.zipf_counts(100, 8, 1.0).sum() == 100


# -- seeded weights ----------------------------------------------------------------

def test_stacked_layer_equals_the_single_layer():
    import jax.numpy as jnp

    key = weights.seed_key(2 ** 31 + 77)
    st = weights.stacked(key, "attn.wq", 3, (8, 4), "fan_in", 0.0,
                         jnp.bfloat16)
    for layer in range(3):
        one = weights.leaf(key, "attn.wq", (8, 4), "fan_in", 0.0,
                           jnp.bfloat16, layer=layer)
        assert (np.asarray(st[layer]) == np.asarray(one)).all()


def test_seed_key_takes_seeds_beyond_32_bits():
    a = np.asarray(weights.seed_key(5))
    b = np.asarray(weights.seed_key(5 + 2 ** 31))
    c = np.asarray(weights.seed_key(5 + 2 ** 40))
    assert not (a == b).all() and not (a == c).all()


@pytest.mark.parametrize("name", ["tiny-decoder", "tiny-mamba2"])
def test_program_tree_fills_every_program_leaf(name):
    import jax

    from bench.lib import harness
    from repro.models import build_model

    config = json.loads((TESTDATA / f"{name}.json").read_text())
    ref = registry.reference(config["reference"], ROOT)
    model = build_model(harness.program_config(config, ref))
    structs = model.param_structs()
    tree = jax.jit(lambda k: weights.program_tree(
        structs, ref.layout(config), k))(weights.seed_key(1))
    for got, want in zip(jax.tree.leaves(tree), jax.tree.leaves(structs)):
        assert got.shape == want.shape and got.dtype == want.dtype


# -- finding files by name ------------------------------------------------------------

def test_every_cell_resolves_its_files():
    bench = _bench()
    for wl in bench["workloads"]:
        config = registry.load_config(bench, wl["config"], ROOT)
        t = registry.load_traffic(wl["traffic"], ROOT)
        registry.runner(t["runner"], ROOT)
        registry.reference(config["reference"], ROOT)
        per_layer = registry.cell_metrics(bench, wl["name"], "per_layer")
        assert per_layer
        for m in per_layer:
            assert callable(registry.metric_reader(m["name"], ROOT))
        e2e = {m["name"] for m in registry.cell_metrics(
            bench, wl["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2


def test_a_cell_added_by_files_alone(tmp_path):
    """A new configuration, traffic mix and per-layer metric need new
    files and new entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = _bench()
    src = tmp_path / "bench"
    cfg = json.loads((src / "configs" / "starcoder2-7b.json").read_text())
    cfg["num_hidden_layers"] = 4
    (src / "configs" / "other-model.json").write_text(json.dumps(cfg))
    (src / "traffic" / "chat-burst.json").write_text(json.dumps(
        dict(_traffic("chat-shared"), rate_per_s=1.0)))
    (src / "metrics" / "queue_depth.py").write_text(
        "def read(ctx):\n    return ctx['counters'].get('depth')\n")
    bench["configs"].append({"name": "other-model", "source": "x",
                             "file": "bench/configs/other-model.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other-model.chat-burst",
                               "config": "other-model",
                               "traffic": "chat-burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "queue_depth.chat", "unit": "1",
                               "better": "lower", "source": "host_clock",
                               "layer": "serving scheduler",
                               "moves": "ttft_p90_ms",
                               "workloads": ["other-model.chat-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = registry.load_benchmark(tmp_path)
    wl = registry.workload(b, "other-model.chat-burst")
    assert registry.load_config(b, wl["config"], tmp_path)[
        "num_hidden_layers"] == 4
    assert registry.load_traffic(wl["traffic"], tmp_path)["rate_per_s"] == 1
    names = [m["name"] for m in registry.cell_metrics(b, wl["name"],
                                                      "per_layer")]
    assert names == ["queue_depth.chat"]
    read = registry.metric_reader("queue_depth.chat", tmp_path)
    assert read({"counters": {"depth": 3}}) == 3
    assert read({"counters": {}}) is None
    with pytest.raises(registry.NotFound):
        registry.metric_reader("no_such_metric", tmp_path)


def test_cell_metrics_without_workloads_follow_their_moves():
    bench = {"end_to_end": [
        {"name": "a", "workloads": ["x"]}, {"name": "setup_s"}],
        "per_layer": [{"name": "p", "moves": "a"},
                      {"name": "q", "moves": "setup_s"},
                      {"name": "r", "moves": "a", "workloads": ["y"]}]}
    assert [m["name"] for m in registry.cell_metrics(
        bench, "x", "per_layer")] == ["p", "q"]
    assert [m["name"] for m in registry.cell_metrics(
        bench, "y", "per_layer")] == ["q", "r"]


def test_metric_readers_return_nothing_without_data():
    bench = _bench()
    empty = {"counters": {}, "trace": None, "peaks": {}, "counts": None,
             "chips": 1}
    for m in bench["per_layer"]:
        assert registry.metric_reader(m["name"], ROOT)(empty) is None


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


# -- the trace reduction ------------------------------------------------------------

def test_trace_reduction_on_a_recorded_tpu_trace():
    path = TESTDATA / "small.xplane.pb"
    red = trace.reduce_file(path)
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    calls, secs = trace.program_seconds(red, "_decode_fn")
    assert calls == 12 and secs > 0
    assert trace.program_seconds(red, "_prefill_fn")[0] == 3
    assert len(red.top_ops) <= 10 and red.top_ops[0][1] > 0
    # the sleeps between steps are the longest gaps, and named so
    assert red.idle_gaps[0][0] == "bench.wait_arrival"
    assert red.idle_gaps[0][1] >= 0.05
    gaps = sum(s for _, s in red.idle_gaps)
    assert gaps <= red.window_s - red.busy_s + 1e-9


def test_trace_union_and_gap_naming():
    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000), Ev("bench.engine_step", 0, 600),
        Ev("bench.wait_arrival", 600, 400)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit__decode_fn(1)", 100, 300)]),
        Line("XLA Ops", [Ev("fusion", 100, 200), Ev("dot", 250, 150)])])
    red = trace.reduce_planes([host, dev])
    assert red.busy_s == pytest.approx(300e-9)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.programs == {"jit__decode_fn(1)": [1.0, pytest.approx(300e-9)]}
    assert red.idle_gaps[0] == ["bench.wait_arrival", pytest.approx(600e-9)]
    assert red.idle_gaps[1] == ["bench.engine_step", pytest.approx(100e-9)]


# -- the entry point -----------------------------------------------------------------

def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_run_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "starcoder2-7b.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=_env(),
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "cpu" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "starcoder2-7b.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=_env(),
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- BENCHMARK.json -------------------------------------------------------------------

def test_benchmark_file_keeps_to_its_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    ok = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
             "0123456789_.-")
    for n in names:
        assert set(n) <= ok and len(n) <= 64
    for wl in b["workloads"]:
        assert len(wl["why"]) <= 200 and wl["chips"] in (1, 4)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert "bound" not in m
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert len(c["why"]) <= 200
