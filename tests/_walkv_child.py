"""Child process for the kill -9 durability test (run by
tests/test_real_runtime.py::TestRealProcessDeath).

Runs a 2-node WAL-KV workload (server node 0 + client node 1) against
real sockets with on-disk stable storage (`RealRuntime(data_dir=...)`),
printing an `ACKED v0 v1` snapshot of the client's per-key acked values
after every poll tick. The parent watches stdout and SIGKILLs this whole
process mid-run — the real-world power-fail the reference's std mode gets
for free from actual files (std/fs.rs:1-60) and the sim models with
page-cache-vs-disk views (fs.py).

argv: data_dir base_port sync|nosync [transport]
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# this workload is real-sockets + real-disk; the chip is irrelevant, so
# force CPU exactly the way tests/conftest.py does
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from madsim_tpu import SimConfig
from madsim_tpu.core.types import ms, sec
from madsim_tpu.models.wal_kv import (WalKvClient, WalKvServer,
                                      wal_persist_spec, wal_state_spec)
from madsim_tpu.real.runtime import RealRuntime


def main():
    data_dir, base_port, sync_flag = (
        sys.argv[1], int(sys.argv[2]), sys.argv[3])
    transport = sys.argv[4] if len(sys.argv) > 4 else "udp"
    cfg = SimConfig(n_nodes=2, time_limit=sec(60))
    # wal_cap larger than total ops: no checkpoint fires, so in the
    # nosync world NOTHING ever reaches the disk view — the red case is
    # deterministic once one ack is out
    rt = RealRuntime(
        cfg,
        [WalKvServer(n_keys=2, wal_cap=64, sync_wal=sync_flag == "sync"),
         WalKvClient(n_ops=40, keys_per_client=2,
                     timeout=ms(80), think=ms(5))],
        wal_state_spec(2, 2, 64, 2), node_prog=[0, 1],
        base_port=base_port, persist=wal_persist_spec(),
        data_dir=data_dir, transport=transport)

    async def scenario():
        await rt.start()
        while True:                     # parent SIGKILLs us mid-loop
            await asyncio.sleep(0.02)
            acked = [int(v) for v in rt.nodes[1].state["acked"]]
            print("ACKED", *acked, flush=True)

    asyncio.run(scenario())


if __name__ == "__main__":
    main()
