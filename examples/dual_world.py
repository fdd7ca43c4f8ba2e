"""The world switch: one protocol, two runtimes.

Runs the same EchoServer/EchoClient programs (a) vectorized in the
simulator over 1024 seeds with faults, then (b) against real asyncio time
and UDP sockets on localhost — the madsim `--cfg madsim` dual-build,
selected at runtime construction.

    python examples/dual_world.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from madsim_tpu import Scenario, SimConfig, NetConfig, ms, sec
from madsim_tpu.harness.simtest import run_seeds
from madsim_tpu.models.rpc_echo import (EchoClient, EchoServer,
                                        make_echo_runtime, server_state_spec)
from madsim_tpu.real.runtime import RealRuntime


def main():
    # --- world 1: the simulator --------------------------------------
    cfg = SimConfig(n_nodes=4, event_capacity=256, time_limit=sec(20),
                    net=NetConfig(packet_loss_rate=0.2))
    sc = Scenario()
    sc.at(ms(30)).kill(0)
    sc.at(sec(1)).restart(0)
    rt = make_echo_runtime(n_nodes=4, target=10, scenario=sc, cfg=cfg)
    state = run_seeds(rt, np.arange(1024), max_steps=40_000)
    acked = np.asarray(state.node_state["acked"])[:, 1:]
    print(f"sim world: 1024 seeds, 20% loss, server kill/restart -> "
          f"all clients acked >= 10: {bool((acked >= 10).all())}")

    # --- world 2: real sockets, same classes -------------------------
    rt2 = RealRuntime(SimConfig(n_nodes=4, time_limit=sec(10)),
                      [EchoServer(), EchoClient(target=10, timeout=ms(50))],
                      server_state_spec(), node_prog=[0, 1, 1, 1],
                      base_port=19500)
    rt2.run(duration=5.0)
    acked = [int(s["acked"]) for s in rt2.states()[1:]]
    print(f"real world: UDP on 127.0.0.1 -> client acks {acked}, "
          f"server served {int(rt2.states()[0]['served'])}")


if __name__ == "__main__":
    main()
