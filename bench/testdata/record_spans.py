#!/usr/bin/env python3
"""Record the small TPU trace of the serving engine's spans that
``bench/tests/test_spans.py`` reads.

    python3 bench/testdata/record_spans.py <out_dir>

Serves a few requests on the ``tiny-decoder`` configuration through
``ServeEngine`` (paged cache, prefix sharing, chunked prefill) under the
profiler, inside a ``bench.window`` annotation, with ``bench.engine_step``
around each step: one request fills the prefix cache before the window,
then two requests share it, one of them diverging inside a shared block
(copy-on-write), while a third arrives later.  The profiler collects as
``bench/lib/harness.py`` has it collect.  Run it on a TPU; it writes
``<out_dir>/spans.xplane.pb``, the profile less what the benchmark's
readers do not read (the compiled programs, the operations'
statistics, host threads other than Python's), for
``bench/testdata/spans.xplane.pb``.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def record(out_dir: str) -> None:
    import contextlib
    import json

    import jax

    import repro
    from bench.lib import harness, registry
    from repro.models import build_model
    from repro.runtime import ServingPolicy
    from repro.serving import Request, ServeEngine

    config = json.loads((ROOT / "bench" / "testdata"
                         / "tiny-decoder.json").read_text())
    ref = registry.reference(config["reference"], ROOT)
    model = build_model(harness.program_config(config, ref))
    params = model.init(jax.random.PRNGKey(0))
    s = config["serving"]
    policy = ServingPolicy(cache="paged", block_size=s["block_size"],
                           prefix=s["prefix"], prefill_chunk=s["chunk"])
    with repro.session(serving=policy):
        engine = ServeEngine(model, params, batch_slots=s["slots"],
                             max_seq=s["max_seq"])
    base = [(7 * i + 3) % 256 for i in range(80)]

    def fill() -> None:
        """Hold only the shared head in the prefix cache."""
        engine.kv.clear_prefix()
        engine.submit(Request(uid=-1, prompt=list(base), max_new_tokens=2))
        engine.run_until_done()

    def serve(uid: int, annotate) -> None:
        for r in (Request(uid=uid, prompt=base[:40] + [1, 2, 3],
                          max_new_tokens=6),
                  Request(uid=uid + 1, prompt=base + [4], max_new_tokens=6)):
            engine.submit(r)
        steps = 0
        while engine.active or engine.waiting or steps < 3:
            if steps == 3:
                engine.submit(Request(uid=uid + 2,
                                      prompt=base[:20] + [5] * 30,
                                      max_new_tokens=4))
            with annotate("bench.engine_step"):
                engine.step()
            steps += 1

    fill()
    serve(-10, lambda _name: contextlib.nullcontext())   # compile all
    fill()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        serve(0, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()


def slim(src: pathlib.Path, dst: pathlib.Path) -> None:
    """Copy the profile ``src`` to ``dst`` with only the device planes
    and the host's Python thread, and no statistics on the operations'
    metadata."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(src.read_bytes())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if not plane.name.startswith(("/device:TPU", "/host:CPU")):
            continue
        kept = out.planes.add()
        kept.CopyFrom(plane)
        if plane.name == "/host:CPU":
            python = [line for line in kept.lines
                      if line.name.startswith("python")]
            del kept.lines[:]
            kept.lines.extend(python)
        for meta in kept.event_metadata.values():
            del meta.stats[:]
    dst.write_bytes(out.SerializeToString())


def main(argv: list[str]) -> int:
    import jax

    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU", file=sys.stderr)
        return 1
    record(argv[0])
    out = pathlib.Path(argv[0])
    slim(max(out.glob("plugins/profile/*/*.xplane.pb")),
         out / "spans.xplane.pb")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
