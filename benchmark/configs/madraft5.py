"""madraft5: 5-node Raft under the 6.824 Lab 2C unreliable churn test.

The lab's loop (20 iterations 700 ms apart: disconnect, restart and
reconnect, crash, each a random server with the lab's chances) is one fixed
draw, kept as `script` in madraft5.json beside this file with every other
size; 10% of messages are lost and each is delayed 0-26 ms until the script
turns the network reliable.
"""

from __future__ import annotations


def build(cfg: dict, control: bool = False):
    """The Runtime this configuration runs. `control=True` builds the same
    deployment with the configuration's `control` fault (a quorum of 2 of
    5), which breaks Raft safety: the correctness check has to fail it."""
    from madsim_tpu import NetConfig, Scenario, SimConfig, ms, sec
    from madsim_tpu.models.raft import make_raft_runtime

    lo, hi = cfg["latency_ms"]
    sim = SimConfig(n_nodes=cfg["n_nodes"],
                    event_capacity=cfg["event_capacity"],
                    time_limit=sec(cfg["time_limit_s"]),
                    payload_words=cfg["payload_words"],
                    net=NetConfig(packet_loss_rate=cfg["packet_loss_rate"],
                                  send_latency_min=ms(lo),
                                  send_latency_max=ms(hi)))
    sc = Scenario()
    for t, what, node in cfg["script"]:
        at = sc.at(ms(t))
        if what == "crash":
            at.kill(node)
        elif what == "restart":
            at.restart(node)
        elif what == "disconnect":
            at.clog_node(node)
        elif what == "connect":
            at.unclog_node(node)
        elif what == "reliable":
            at.set_loss(0.0)
            rlo, rhi = cfg["reliable_latency_ms"]
            at.set_latency(ms(rlo), ms(rhi))
        else:
            raise ValueError(f"unknown script op {what!r}")
    extra = dict(cfg["control"]) if control else {}
    return make_raft_runtime(cfg["n_nodes"], log_capacity=cfg["log_capacity"],
                             n_cmds=cfg["n_cmds"], scenario=sc, cfg=sim,
                             **extra)
