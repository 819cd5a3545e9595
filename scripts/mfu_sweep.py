"""MFU sweep driver: probe bench.py configurations on the real chip.

Each configuration runs ``bench.py`` in a timeout-bounded subprocess with
the config exported through the BENCH_* env knobs; this parent never imports
jax, so each child has the chip to itself. Prints a ranked table and the
best config's JSON line. Run it through the chip tool as one command.

Usage:
    python scripts/mfu_sweep.py                    # default grid
    python scripts/mfu_sweep.py --timeout 600
    python scripts/mfu_sweep.py --grid '[{"BENCH_LOSS_CHUNK": 128}, ...]'
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the VERDICT r2 margin targets: lm-head chunk under the fused CE, and the
# flash tile shapes at batch 12 (each run ~3-6 min incl. compile)
DEFAULT_GRID = [
    {},  # committed defaults (chunk 256, tiles 1024x1024, batch 12)
    {"BENCH_LOSS_CHUNK": "128"},
    {"BENCH_LOSS_CHUNK": "512"},
    {"BENCH_FLASH_BQ": "2048", "BENCH_FLASH_BKV": "1024"},
    {"BENCH_FLASH_BQ": "1024", "BENCH_FLASH_BKV": "2048"},
    {"BENCH_FLASH_BQ": "512", "BENCH_FLASH_BKV": "1024"},
    {"BENCH_BATCH": "13"},
    # margin candidates past the 46.4% point (VERDICT r4 weak #1: bank a
    # >=48% config): full-2048 tiles continue the "bigger tiles amortize
    # Mosaic overhead" trend that carried 256x512 -> 1024x1024; chunk 1024
    # probes the bigger-chunk direction. Chunk probes must divide 2048 —
    # the loss sequence is 2047 tokens and fused CE pads to a chunk
    # multiple, so a non-divisor (e.g. 384 -> padded 2304) would bank a
    # padding-waste artifact, not the chunk-size tradeoff
    {"BENCH_FLASH_BQ": "2048", "BENCH_FLASH_BKV": "2048"},
    {"BENCH_LOSS_CHUNK": "1024"},
]


def run_one(overrides: dict, timeout_s: float):
    env = dict(os.environ)
    env.update({k: str(v) for k, v in overrides.items()})
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": "timeout"}
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()
        return {"error": tail[-1][:200] if tail else f"rc={proc.returncode}"}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return {"error": "no JSON line in output"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--grid", default=None, help="JSON list of env-override dicts")
    args = ap.parse_args()
    grid = json.loads(args.grid) if args.grid else DEFAULT_GRID

    results = []
    for overrides in grid:
        label = ",".join(f"{k.replace('BENCH_', '')}={v}"
                         for k, v in overrides.items()) or "defaults"
        print(f"# running {label} ...", flush=True)
        rec = run_one(overrides, args.timeout)
        mfu = rec.get("detail", {}).get("mfu")
        print(f"#   -> {'mfu=%.4f' % mfu if mfu else rec.get('error')}",
              flush=True)
        results.append((label, mfu, rec))

    results.sort(key=lambda r: (r[1] is None, -(r[1] or 0)))
    print(f"\n{'config':<40}{'mfu':>8}{'tok/s':>10}{'step_ms':>10}")
    for label, mfu, rec in results:
        if mfu is None:
            print(f"{label:<40}{'—':>8}  {rec.get('error', '')[:40]}")
        else:
            d = rec["detail"]
            print(f"{label:<40}{mfu:>8.4f}{rec['value']:>10.0f}"
                  f"{d['step_ms']:>10.1f}")
    best = results[0]
    if best[1] is not None:
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        print("\nbest:", best[0])
        print("# BASELINE.md north star is 45% MFU (peaks: flops.CHIP_PEAKS)")
        print(json.dumps(best[2]))


if __name__ == "__main__":
    main()
