"""Record the small device trace the xplane tests read, on the chip:

    chiprun -- python3 benchmarks/tools/record_tiny_trace.py

Two steps of a tiny jitted program (a matmul, a named Pallas kernel, an
update), a host pause between them so the trace holds an idle gap, host spans
around each step. Writes ``chiprun_out/tiny_trace.xplane.pb`` (copy it to
``benchmarks/testdata/``) and prints the trace's planes, lines and first
event names, which is how the reduction in ``xplane.py`` was written: look at
a trace by hand before writing code against it."""

import glob
import os
import shutil
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def dump(path, limit=6):
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                stats = {k: (str(v)[:60]) for k, v in list(ev.stats)[:8]}
                print(f"    {ev.name[:90]!r} start_ns={ev.start_ns:.0f} dur_ns={ev.duration_ns:.0f} {stats}")


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: this records a device trace")

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def tiny_step(w, x):
        h = jnp.tanh(x @ w)
        y = pl.pallas_call(
            double, out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype), name="tiny_double",
        )(h)
        return w - 1e-3 * (x.T @ y), jnp.sum(y)

    w = jnp.ones((512, 512), jnp.bfloat16)
    x = jnp.ones((1024, 512), jnp.bfloat16)
    w, loss = tiny_step(w, x)
    jax.block_until_ready(loss)
    out = os.path.join("chiprun_out", "tiny_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for i in range(2):
        with jax.profiler.TraceAnnotation("bench_step", step=i):
            w, loss = tiny_step(w, x)
            jax.block_until_ready(loss)
        with jax.profiler.TraceAnnotation("bench_pause"):
            time.sleep(0.005)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
    kept = os.path.join("chiprun_out", "tiny_trace.xplane.pb")
    shutil.copy(path, kept)
    print(f"{kept}: {os.path.getsize(kept)} bytes")
    dump(kept, limit=int(sys.argv[1]) if len(sys.argv) > 1 else 6)


if __name__ == "__main__":
    main()
