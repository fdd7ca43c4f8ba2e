"""AOT stage timers: where does getting-to-execution time actually go?

`bench.py --mode compile_ab` uses these to decompose a runner's cold cost
into trace -> lower -> compile (the jax AOT pipeline) plus first-execute,
instead of reporting one opaque "warmup" number.
"""

from __future__ import annotations

import time
from typing import Any


def timed_stages(jitted, *args, **kwargs) -> dict[str, Any]:
    """Run the AOT pipeline of a jitted callable on `args`, timing each
    stage. Returns {trace_s, lower_s, compile_s, total_s, compiled}.

    NOTE: jax's AOT objects do not seed the jitted function's own
    dispatch cache — use the returned `compiled` for execution, or
    accept one more (cached-by-XLA-persistent-layer) compile on the
    first ordinary call."""
    t0 = time.perf_counter()
    traced = jitted.trace(*args, **kwargs)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    compiled = lowered.compile()
    t3 = time.perf_counter()
    return dict(
        trace_s=t1 - t0,
        lower_s=t2 - t1,
        compile_s=t3 - t2,
        total_s=t3 - t0,
        compiled=compiled,
    )
