"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the machine it is started on: builds
the system from ``--seed``, checks it against the plain reference, warms up
every shape the cell's traffic uses (all of that is ``setup_s``), measures for
``--seconds``, and prints one JSON object as the last line of its output. With
``--trace 0`` the metrics are the cell's end-to-end metrics; with ``--trace 1``
a few seconds are profiled and the metrics are the cell's per-layer metrics.

It refuses to run without a TPU. ``--rehearse-on-cpu N`` is the CPU rehearsal:
the same code at tiny sizes on N virtual CPU devices; it says so, and prints no
number under a metric's own name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# Started as a script, Python puts this directory first on the path, where
# profile.py, stats.py and traffic.py would shadow modules of those names (the
# standard library's `profile` among them): the repo's root takes its place.
_HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.abspath(sys.path[0] or os.getcwd()) == _HERE:
    sys.path[0] = os.path.dirname(_HERE)
elif os.path.dirname(_HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(_HERE))

from benchmarks import spec  # noqa: E402

# the host spans the benchmark wraps around its own calls; idle gaps on the
# device are attributed to the one they fall in
HOST_SPANS = (
    "generator_wait", "engine.step", "make_batch", "train_step_dispatch", "wait_loss",
)
REHEARSAL_PREFIX = "cpu_rehearsal."


def result_line(correct, attempted, failed, metrics, device, breakdown=None) -> str:
    """The last line of stdout, to the driver's contract."""
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {
            name: {"value": float(value), "unit": unit}
            for name, (value, unit) in metrics.items()
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", type=int, metavar="N", default=0,
                    help="tiny sizes on N virtual CPU devices; not a device result")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (its data files are found beside it)")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_on_cpu > 0

    cell = spec.load_cell(args.workload, args.benchmark_json)
    if rehearsal:
        # the Pallas kernels themselves, interpreted (as chip_smoke.py's
        # rehearsal): the jnp twins of the "reference" mode are another program
        os.environ["NXDT_KERNEL_MODE"] = "interpret"
        cell = cell.for_rehearsal()

    import jax

    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        device_summary, enable_compile_cache, require_tpu, set_cpu_devices,
    )

    from benchmarks import peaks, stats, xplane

    if rehearsal:
        set_cpu_devices(args.rehearse_on_cpu)
        device = device_summary()
        chip_peaks = None
        print("REHEARSAL ON CPU - tiny sizes on virtual devices; nothing below "
              "is a device result", flush=True)
    else:
        device = require_tpu()          # raises: no result line without a TPU
        chip_peaks = peaks.peaks_for(device["kind"])   # raises for an unknown chip
    if device["count"] < cell.chips:
        raise SystemExit(
            f"{cell.name} needs {cell.chips} chips, JAX found {device['count']}"
        )
    # cache every program, however quick its compile: the cell's small
    # programs would otherwise compile again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    print(f"device: {device}; compile cache: {enable_compile_cache()}", flush=True)

    family = spec.load_family(cell.config["family"])
    seconds = float(args.seconds if args.seconds is not None else 10.0)
    split = {}
    if cell.traffic["kind"] == "train_job":
        from benchmarks import training as runner
    else:
        from benchmarks import serving as runner
    result = runner.run(cell, family, args.seed, seconds, rehearsal, bool(args.trace), split, T_PROCESS)
    result["peaks"] = chip_peaks
    result["notes"] = []

    prof = result.get("profile")
    reduced = None
    if prof and prof.get("xplane"):
        reduced = xplane.reduce(prof["xplane"], annotations=HOST_SPANS)
    result["reduced"] = reduced

    rows = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for row in rows:
        reader = spec.load_metric(
            "layer_metrics" if args.trace else "end_to_end", row["name"], cell.root
        )
        value = reader(result)
        if value is None:
            result["notes"].append(f"{row['name']}: nothing to read, left out")
            continue
        metrics[row["name"]] = (value, row["unit"])
    if not args.trace and len(metrics) != len(rows):
        result["problems"].append("an end-to-end metric could not be read")
        result["correct"] = False

    print(f"set-up {result['setup_s']:.1f} s: " + ", ".join(
        f"{k[:-2]} {v:.1f}" for k, v in split.items()), flush=True)
    print(f"check: {json.dumps(result['check'])}")
    for text in result["failures"][:10] + result["problems"] + result["notes"]:
        print(f"note: {text}")
    if result["kind"] == "serving":
        done = sum(1 for s in result["in_window"] if s.done is not None)
        print(f"requests due in the window {result['attempted']}, completed {done}, "
              f"failed {result['failed']}, unfinished at the drain limit {result['unfinished']}; "
              f"highest percentile with ten samples beyond it: "
              f"p{stats.highest_supported_percentile(done) or 0:.0f}")
    else:
        print(f"steps in the window {result['attempted']}, losses "
              f"{[round(x, 4) for x in result['losses'][:4]]} ... {round(result['losses'][-1], 4)}")

    device_out = dict(device)
    device_out["memory_peak_bytes"] = result["memory_peak_bytes"]
    breakdown = None
    if args.trace:
        if reduced and reduced.get("devices"):
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
            }
            print(f"profiler: start {prof['start_cost_s']:.2f} s, stop {prof['stop_cost_s']:.2f} s")
        elif not rehearsal:
            raise SystemExit("the traced run holds no device operation")
    if rehearsal:
        metrics = {REHEARSAL_PREFIX + k: v for k, v in metrics.items()}
        print("REHEARSAL ON CPU - the line below names the host, and its metrics "
              f"carry the prefix {REHEARSAL_PREFIX!r}: they are not device numbers")
    print(result_line(
        result["correct"], result["attempted"], result["failed"], metrics,
        device_out, breakdown,
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
