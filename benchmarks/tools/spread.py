"""Measure a cell's spread as the driver does, on the chip, in one call:

    chiprun [--chips 4] --timeout 3000 -- python3 benchmarks/tools/spread.py <workload> [runs] [first seed]

runs the benchmark's command ``runs`` times (default 12: two sets of six), a
fresh process and another ``--seed`` each time, at ``run_seconds``, appends each
result line to ``chiprun_out/spread_<workload>.jsonl``, and prints for every
end-to-end metric each set's median and spread (distance between the quartiles
over the median), the wider of the two, and five times it — the bound the
contract suggests. This parent never touches JAX, so each child gets the chip."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT

from benchmarks import stats  # noqa: E402


def spread(values):
    med = stats.median(values)
    return (stats.percentile(values, 75) - stats.percentile(values, 25)) / med, med


def main():
    workload = sys.argv[1]
    runs = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    seed0 = int(sys.argv[3]) if len(sys.argv) > 3 else 100
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = os.path.join(ROOT, "chiprun_out", f"spread_{workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    lines = []
    for i in range(runs):
        proc = subprocess.run(
            [*bench["command"], "--workload", workload, "--seed", str(seed0 + i),
             "--seconds", str(bench["run_seconds"]), "--trace", "0"],
            cwd=ROOT, capture_output=True, text=True,
        )
        last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        if proc.returncode != 0 or not last.startswith("{"):
            print(f"run {i}: exit {proc.returncode}\n{proc.stderr[-1500:]}", flush=True)
            continue
        line = json.loads(last)
        lines.append(line)
        with open(out, "a") as f:
            f.write(last + "\n")
        print(f"run {i} seed {seed0 + i}: correct {line['correct']} attempted "
              f"{line['attempted']} failed {line['failed']} " + " ".join(
                  f"{k}={v['value']:.4f}" for k, v in line["metrics"].items())
              + f" peak {line['device']['memory_peak_bytes'] / 1e9:.2f} GB", flush=True)
        if not line["correct"]:     # say why: the run's own notes and the end of its log
            print("\n".join(x for x in proc.stdout.splitlines() if x.startswith("note: ")), flush=True)
            print(proc.stderr[-3000:], flush=True)
    half = len(lines) // 2
    for name in lines[0]["metrics"] if lines else ():
        sets = [[l["metrics"][name]["value"] for l in part] for part in (lines[:half], lines[half:])]
        (s1, m1), (s2, m2) = spread(sets[0]), spread(sets[1])
        print(f"{name}: set 1 median {m1:.4f} spread {100 * s1:.2f} %, set 2 median {m2:.4f} "
              f"spread {100 * s2:.2f} %, medians differ {100 * (m2 - m1) / m1:+.2f} %, "
              f"wider spread {100 * max(s1, s2):.2f} % -> x5 = {500 * max(s1, s2):.1f} %")


if __name__ == "__main__":
    main()
