"""Schema-first service definition — the tonic-build workflow, both worlds.

The reference defines RPC services in .proto files and generates the
client/server API at build time (madsim-tonic-build). Here the same
schema shape generates a Python module (`python -m madsim_tpu.net.codegen
schema.proto -o schema_pb.py`, or `generate()` in-process as below), and
the implementation runs UNCHANGED in the batched simulator and against
real sockets.

Run:  python examples/codegen_service.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np

from madsim_tpu import Program, Runtime, SimConfig, NetConfig, ms, sec
from madsim_tpu.harness.simtest import run_seeds
from madsim_tpu.net import codegen, rpc

SCHEMA = """
syntax = "proto3";

message PutReq { int32 key = 1; int32 val = 2; }
message PutRsp { int32 ok = 1; }
message GetReq { int32 key = 1; }
message GetRsp { int32 val = 1; int32 found = 2; }

service Store {
  rpc Put(PutReq) returns (PutRsp);
  rpc Get(GetReq) returns (GetRsp);
}
"""

# generate + load the module (a build would write this to store_pb.py)
pb = {}
exec(compile(codegen.generate(SCHEMA), "store_pb.py", "exec"), pb)

N_KEYS = 4


class StoreImpl(pb["StoreBase"]):
    """Fill in the generated handle_* hooks; everything else —
    tag hashing, dispatch, unpack/pack, reply routing — is generated."""

    def handle_put(self, ctx, st, req, when):
        k = jnp.clip(req["key"], 0, N_KEYS - 1)
        onehot = jnp.arange(N_KEYS) == k
        st["kv"] = jnp.where(onehot & when, req["val"], st["kv"])
        st["has"] = st["has"] | (onehot & when)
        return dict(ok=jnp.asarray(when, jnp.int32))

    def handle_get(self, ctx, st, req, when):
        k = jnp.clip(req["key"], 0, N_KEYS - 1)
        onehot = jnp.arange(N_KEYS) == k
        return dict(val=jnp.where(onehot, st["kv"], 0).sum(),
                    found=(st["has"] & onehot).any().astype(jnp.int32))


T_RETRY = 1


class Client(Program):
    """put(k, 100+k) for each key, then get(0) and halt on found."""

    def init(self, ctx):
        st = dict(ctx.state)
        st["call_id"] = rpc.new_call_id(ctx)
        pb["store_put"](ctx, 0, st["call_id"], retry_timer_tag=T_RETRY,
                        timeout=ms(40), key=0, val=100)
        ctx.state = st

    def _issue(self, ctx, st, step, call_id, when):
        done_puts = step >= N_KEYS
        k = jnp.clip(step, 0, N_KEYS - 1)
        pb["store_put"](ctx, 0, call_id, retry_timer_tag=T_RETRY,
                        timeout=ms(40), key=k, val=100 + k,
                        when=when & ~done_puts)
        pb["store_get"](ctx, 0, call_id, retry_timer_tag=T_RETRY,
                        timeout=ms(40), key=0, when=when & done_puts)

    def on_timer(self, ctx, tag, payload):
        st = dict(ctx.state)
        retry = (tag == T_RETRY) & (payload[0] == st["call_id"])
        self._issue(ctx, st, st["step"], st["call_id"], retry)
        ctx.state = st

    def on_message(self, ctx, src, tag, payload):
        st = dict(ctx.state)
        hit = rpc.is_reply(tag) & rpc.matches(payload, st["call_id"])
        is_get = tag == rpc.reply_tag(pb["StoreBase"].Get.tag)
        get_rsp = pb["unpack_get_rsp"](payload[1:])
        ctx.crash_if(hit & is_get & (get_rsp["val"] != 100), 7)
        st["step"] = st["step"] + hit
        new_id = rpc.new_call_id(ctx)
        self._issue(ctx, st, st["step"], new_id, hit & ~is_get)
        st["call_id"] = jnp.where(hit & ~is_get, new_id, st["call_id"])
        ctx.halt_if(hit & is_get & (ctx.node == 1))
        ctx.state = st


def spec():
    z = jnp.asarray(0, jnp.int32)
    return dict(kv=jnp.zeros((N_KEYS,), jnp.int32),
                has=jnp.zeros((N_KEYS,), bool), call_id=z, step=z)


def main():
    cfg = SimConfig(n_nodes=2, time_limit=sec(20),
                    net=NetConfig(packet_loss_rate=0.1))
    rt = Runtime(cfg, [StoreImpl(), Client()], spec(), node_prog=[0, 1])
    state = run_seeds(rt, np.arange(64), max_steps=20_000)
    kv = np.asarray(state.node_state["kv"])[:, 0]
    print(f"64 seeds under 10% loss: all halted={bool(state.halted.all())}, "
          f"store contents (seed 0): {kv[0].tolist()}")
    assert (kv == [100, 101, 102, 103]).all()
    print("generated service OK: schema -> Layouts + dispatch + client "
          "stubs, protocol logic only in handle_put/handle_get")


if __name__ == "__main__":
    main()
