"""The comparison that decides ``correct`` for a serving cell: what it reads
from the engine, and that it fails what it has to fail."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import check, serving, spec


def test_token_deficit_is_zero_at_the_argmax_and_large_for_any_other_token():
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((9, 32000)).astype(np.float32)
    best = rows.argmax(-1).tolist()
    assert check.token_deficits(rows, best).max() == 0.0
    # a sampler that emits something else sits about four deviations down
    garbage = check.token_deficits(rows, rng.integers(0, 32000, 9).tolist())
    assert np.median(garbage) > 3.0 and (garbage <= check.TOKEN_DEFICIT).mean() < check.TOKEN_SHARE
    # a near tie, as rounding makes them, passes
    second = np.argsort(rows, axis=-1)[:, -2].tolist()
    assert check.token_deficits(rows, second).max() < check.TOKEN_DEFICIT


def test_rungs_are_picked_as_the_engine_picks_them():
    assert [check._rung([128, 512, 2304], n) for n in (1, 128, 129, 600, 9999)] == [128, 128, 512, 2304, 2304]


class _Engine:
    """The serving engine's request surface, emitting one token a step."""

    def __init__(self, fail_at=None):
        self.out, self.done, self.cancelled, self.steps, self.fail_at = [], False, None, 0, fail_at

    def submit(self, prompt, service_class="batch"):
        self.prompt, self.service_class = list(prompt), service_class
        return 7

    def step(self):
        if self.done:
            return False
        self.steps += 1
        self.out.append(100 + len(self.out))
        if self.fail_at is not None and len(self.out) >= self.fail_at:
            self.done = True
        return True

    def request_tokens(self, rid):
        assert rid == 7
        return list(self.out)

    def request_info(self, rid):
        failed = self.done and self.cancelled is None
        return {"done": self.done, "status": "failed" if failed else "active",
                "error": "device fault" if failed else None}

    def cancel(self, rid, reason=""):
        self.done, self.cancelled = True, reason


def test_engine_tokens_come_through_submit_and_step_and_the_request_is_cancelled():
    engine = _Engine()
    run = check.engine_tokens(engine, [1, 2, 3], 5, "interactive")
    assert run == {"tokens": [100, 101, 102, 103, 104], "error": None}
    assert engine.service_class == "interactive" and engine.cancelled and engine.steps == 5
    failed = check.engine_tokens(_Engine(fail_at=2), [1, 2, 3], 5, "batch")
    assert failed["error"] == "device fault" and len(failed["tokens"]) == 2


def _sample(i, **kw):
    return serving.Sample(i, 100, None, due=1.0, **kw)


def test_a_request_unfinished_at_the_drain_limit_is_a_failed_request():
    ok = _sample(0, done=2.0, tokens=8)
    short = _sample(1, done=2.0, tokens=7)
    refused = _sample(2, error="HTTP 429")
    hanging = _sample(3, sent=1.0, first_token=1.5)
    failures = serving.window_failures([ok, short, refused, hanging], want=8)
    assert len(failures) == 3 and "unfinished at the drain limit" in failures[2]
    assert serving.window_failures([ok], want=8) == []


@pytest.mark.parametrize("kv,ok", [(None, True), ("int8", False)])
def test_the_check_passes_the_engine_as_shipped_and_fails_an_8_bit_pool(kv, ok):
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "tools", "check_calibrate.py"),
         "mixtral-docs-batch", "--seed", "5", "--rehearse-on-cpu", "1", *(["--kv", kv] if kv else [])],
        capture_output=True, text=True, timeout=600, cwd=spec.REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(x for x in proc.stdout.splitlines() if x.startswith("seed 5"))
    checked = json.loads(line[line.index(": {") + 2:])
    assert checked["ok"] is ok
    assert checked["cache"]["plain_pool_is_own"] is ok
    assert checked["engine_tokens"]["n"] == 4 and checked["engine_tokens"]["near_reference_max"] == 1.0
    assert (checked["cache"]["p50"] > checked["cache"]["tolerance"]) is (not ok)
