"""Record the small device trace that benchmark/selftest.py checks the trace
reduction against. Run once on the chip, by hand:

    chiprun -- python3 benchmark/tools/record_fixture.py

One process that holds the chip (this is a tool, not a cell): under
`bench:window`, three rounds of 20 ms of sleep under `host:batch_wait`, each
followed by a jitted chain of four matmuls under `host:forward`. Writes chiprun_out/fixture/trace.xplane.pb and
events.json (every device event and host annotation, as read back), from
which fixtures/expected.json was worked out.
"""
import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

OUT = os.path.join("chiprun_out", "fixture")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1

    @jax.jit
    def chain(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.full((2048, 2048), 0.01, jnp.bfloat16)
    chain(x, w).block_until_ready()
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(OUT, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("host:batch_wait"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("host:forward"):
                chain(x, w).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(OUT, "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(OUT, "trace.xplane.pb"))
    shutil.rmtree(os.path.join(OUT, "plugins"))

    data = jax.profiler.ProfileData.from_file(
        os.path.join(OUT, "trace.xplane.pb"))
    dump = []
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            keep = (plane.name.startswith("/device:")
                    or any(n.startswith("host:") for n, _, _ in events))
            dump.append({"plane": plane.name, "line": line.name,
                         "n_events": len(events),
                         "events": events[:400] if keep else events[:3]})
    with open(os.path.join(OUT, "events.json"), "w") as f:
        json.dump(dump, f)
    for d in dump:
        print(d["plane"], "|", d["line"], "|", d["n_events"],
              d["events"][:2])
    print("bytes", os.path.getsize(os.path.join(OUT, "trace.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
