"""Taking a device trace: host spans the benchmark puts around its own calls
(``jax.profiler.TraceAnnotation``, which costs next to nothing while no trace
is being taken), and the capture of a few seconds into a directory inside the
checkout, one per process, so that two traced runs in one checkout (the tests
run several at once) do not delete each other's trace."""

from __future__ import annotations

import asyncio
import glob
import os
import shutil
import time
from typing import Any, Callable, Dict, Optional

from benchmarks.spec import REPO_ROOT

TRACE_DIR = os.path.join(REPO_ROOT, ".bench_out", "trace")


def _run_dir() -> str:
    return os.path.join(TRACE_DIR, str(os.getpid()))


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _start() -> float:
    import jax

    shutil.rmtree(_run_dir(), ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the Python tracer slows the host loop
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    jax.profiler.start_trace(_run_dir(), profiler_options=opts)
    return t0


def _stop(t_call: float, t_started: float, extra: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    t_end = time.perf_counter()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(_run_dir(), "plugins", "profile", "*", "*.xplane.pb"))
    return {
        "xplane": files[0] if files else None,
        "host_window": (t_started, t_end),     # perf_counter, profiler running
        "start_cost_s": t_started - t_call,
        "stop_cost_s": time.perf_counter() - t_end,
        **extra,
    }


def capture(work: Callable[[], None]) -> Dict[str, Any]:
    """Run ``work()`` under the profiler."""
    t_call = _start()
    t_started = time.perf_counter()
    work()
    return _stop(t_call, t_started, {})


async def capture_async(seconds: float, steps_of: Optional[Any] = None) -> Dict[str, Any]:
    """Profile the next ``seconds`` of an event loop's work. Called from a
    task of the loop that also steps the engine, so it starts and stops
    between engine steps. ``steps_of`` is the engine's ``EngineTracer``: its
    step records taken while the profiler ran are returned as
    ``engine_steps``."""
    t_call = _start()
    t_started = time.perf_counter()
    await asyncio.sleep(seconds)
    t_end = time.perf_counter()
    steps = []
    if steps_of is not None:
        steps = [s for s in list(steps_of._steps) if s["t0"] >= t_started and s["t1"] <= t_end]
    return _stop(t_call, t_started, {"engine_steps": steps})
