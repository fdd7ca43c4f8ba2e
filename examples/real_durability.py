"""Real-world durability demo: the WAL-KV server survives kill -9.

    python examples/real_durability.py [data_dir]

Phase 1 runs a WAL-KV server + client over real UDP sockets with
on-disk stable storage (`RealRuntime(data_dir=...)` — the std/fs.rs
twin: fs disk views spilled with fsync + atomic rename after every
event). Phase 2 "power-fails" by constructing a COMPLETELY FRESH
runtime over the same data_dir — exactly what a new OS process sees —
and shows the server's recovery (mount, load checkpoint, replay WAL)
observing every previously-acked write. tests/test_real_runtime.py
does the honest version with a real SIGKILLed child process.
"""

import asyncio
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# real sockets + real disk: the accelerator is irrelevant, so force the
# host platform
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from madsim_tpu import SimConfig
from madsim_tpu.core.types import ms, sec
from madsim_tpu.models.wal_kv import (WalKvClient, WalKvServer,
                                      wal_persist_spec, wal_state_spec)
from madsim_tpu.real.runtime import RealRuntime


def make_rt(data_dir, port):
    cfg = SimConfig(n_nodes=2, time_limit=sec(30))
    return RealRuntime(
        cfg, [WalKvServer(n_keys=2, wal_cap=64),
              WalKvClient(n_ops=8, keys_per_client=2,
                          timeout=ms(80), think=ms(10))],
        wal_state_spec(2, 2, 64, 2), node_prog=[0, 1], base_port=port,
        persist=wal_persist_spec(), data_dir=data_dir)


def main():
    data_dir = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp(
        prefix="walkv_demo_")
    print(f"stable storage: {data_dir}")

    rt = make_rt(data_dir, 19800)
    rt.run(duration=3.0)
    acked = [int(v) for v in rt.states()[1]["acked"]]
    kv_mem = [int(v) for v in rt.states()[0]["kv"]]
    print(f"phase 1: client acked per-key values {acked}; "
          f"server kv (memory) {kv_mem}")

    # phase 2: a fresh runtime = a fresh process image; only the disk
    # survives. Server init recovers: mount, load DB, replay WAL.
    rt2 = make_rt(data_dir, 19820)

    async def boot():
        await rt2.start(nodes=[0])    # server only: recovery, no new ops
        rt2.kill(0)

    asyncio.run(boot())
    kv_disk = [int(v) for v in rt2.states()[0]["kv"]]
    print(f"phase 2: recovered kv after simulated kill -9 {kv_disk}")
    ok = all(d >= a for d, a in zip(kv_disk, acked))
    print("durability holds: every acked write recovered"
          if ok else "DURABILITY VIOLATION")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
