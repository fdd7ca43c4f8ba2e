"""Real-ecosystem wire interop: a gRPC gateway in front of a RealRuntime
service.

When the reference builds for production, madsim-tonic re-exports REAL
tonic (madsim-tonic/src/lib.rs:7-8) — its services are wire-compatible
with any gRPC peer. This runtime's real twin natively speaks its own
`[tag, src, payload-words]` datagram format (real/runtime.py), so
third-party interop goes through a GATEWAY: a stock grpcio server
(HTTP/2 on a TCP port — the standard gRPC wire) that adapts each method
of a net/codegen.py-generated service onto the runtime's wire format.

The demo is three parties:
  backend  — a separate OS process running RealRuntime + the generated
             Store service (the same StoreImpl shape as
             examples/codegen_service.py), node 0 on UDP base_port.
  gateway  — THIS process: grpc.server() with one generic handler per
             schema method; a gRPC request's bytes are the request
             message's int32 words (little-endian, field order — exactly
             the generated Layout), forwarded as a framework datagram
             from gateway node id 1, reply matched by call id.
  client   — a vanilla grpcio channel. It does NOT import the framework:
             it packs requests with plain struct from the schema alone —
             the third-party-peer proof.

Run:  python examples/grpc_gateway.py
Skips (exit 0 with a note) if grpcio is not installed.
"""

import itertools
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

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

BASE_PORT = 19820        # UDP: node 0 = backend, node 1 = gateway
GRPC_PORT = 19840        # TCP: the standard gRPC wire
PAYLOAD_WORDS = 8
N_KEYS = 4
REPLY_BIT = 1 << 30


# ---------------------------------------------------------------- backend
def backend_main(duration: float):
    """Child-process entry: the RealRuntime service node."""
    import asyncio

    import jax.numpy as jnp

    from madsim_tpu import SimConfig, sec
    from madsim_tpu.net import codegen
    from madsim_tpu.real.runtime import RealRuntime

    pb = {}
    exec(compile(codegen.generate(SCHEMA), "store_pb.py", "exec"), pb)

    class StoreImpl(pb["StoreBase"]):
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

    spec = dict(kv=jnp.zeros((N_KEYS,), jnp.int32),
                has=jnp.zeros((N_KEYS,), jnp.bool_))
    # n_nodes=2 but ONLY node 0 starts: node 1's address belongs to the
    # external gateway process (start(nodes=[0]) leaves its port unbound)
    rt = RealRuntime(SimConfig(n_nodes=2, payload_words=PAYLOAD_WORDS,
                               time_limit=sec(600)),
                     [StoreImpl()], spec, node_prog=[0, 0],
                     base_port=BASE_PORT)

    async def main():
        await rt.start(nodes=[0])
        print("backend: ready", flush=True)
        await asyncio.sleep(duration)

    asyncio.run(main())


# ---------------------------------------------------------------- gateway
class UdpBridge:
    """One UDP socket at the gateway's node address; serialized
    request/reply round-trips into the runtime's wire format."""

    def __init__(self, methods):
        self.methods = methods           # path -> (tag, req_w, rsp_w)
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", BASE_PORT + 1))
        self.sock.settimeout(1.0)
        self.lock = threading.Lock()
        self.call_ids = itertools.count(1)

    def round_trip(self, path: str, req_bytes: bytes) -> bytes:
        tag, req_w, rsp_w = self.methods[path]
        assert len(req_bytes) == 4 * req_w, \
            f"{path}: want {4 * req_w} request bytes, got {len(req_bytes)}"
        body = struct.unpack(f"<{req_w}i", req_bytes) if req_w else ()
        with self.lock:
            call_id = next(self.call_ids)
            payload = (call_id,) + body
            payload += (0,) * (PAYLOAD_WORDS - len(payload))
            frame = struct.pack(f"<ii{PAYLOAD_WORDS}i", tag, 1, *payload)
            for _ in range(5):           # UDP: retry on (unlikely) loss
                self.sock.sendto(frame, ("127.0.0.1", BASE_PORT))
                try:
                    while True:
                        data, _ = self.sock.recvfrom(65536)
                        if len(data) != 8 + 4 * PAYLOAD_WORDS:
                            continue
                        rtag, _src, *words = struct.unpack(
                            f"<ii{PAYLOAD_WORDS}i", data)
                        if rtag == (tag | REPLY_BIT) and words[0] == call_id:
                            return struct.pack(
                                f"<{rsp_w}i", *words[1:1 + rsp_w])
                except socket.timeout:
                    continue
        raise TimeoutError(f"no reply from backend for {path}")


def make_gateway(methods):
    import grpc
    bridge = UdpBridge(methods)

    class Handler(grpc.GenericRpcHandler):
        def service(self, call_details):
            path = call_details.method
            if path not in methods:
                return None

            def behavior(request, context, path=path):
                return bridge.round_trip(path, request)

            # bytes in/out: the message format is the schema's int32
            # words — any gRPC stack that can send bytes interoperates
            return grpc.unary_unary_rpc_method_handler(behavior)

    from concurrent import futures
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((Handler(),))
    server.add_insecure_port(f"127.0.0.1:{GRPC_PORT}")
    return server, bridge


def schema_methods():
    """path -> (tag, req_words, rsp_words), derived from the schema the
    same way the backend derives it (exec the generated module)."""
    from madsim_tpu.net import codegen
    pb = {}
    exec(compile(codegen.generate(SCHEMA), "store_pb.py", "exec"), pb)
    messages, services = codegen.parse(SCHEMA)
    out = {}
    for sname, rpcs in services.items():
        base = pb[f"{sname}Base"]
        for meth, req, _rs, rsp, _ps in rpcs:
            out[f"/store.{sname}/{meth}"] = (
                getattr(base, meth).tag, len(messages[req]),
                len(messages[rsp]))
    return out


# ---------------------------------------------------------------- client
def third_party_client():
    """A vanilla gRPC caller: no framework imports, just the schema.
    Returns the observed results dict."""
    import grpc
    ch = grpc.insecure_channel(f"127.0.0.1:{GRPC_PORT}")
    put = ch.unary_unary("/store.Store/Put")
    get = ch.unary_unary("/store.Store/Get")
    deadline = time.time() + 20
    while True:      # backend's jax import takes a few seconds; retry
        try:
            put(struct.pack("<2i", 0, 100), timeout=8)
            break
        except grpc.RpcError:
            if time.time() > deadline:
                raise
            time.sleep(0.5)
    put(struct.pack("<2i", 1, 101), timeout=8)
    out = {}
    for k in (0, 1, 3):
        val, found = struct.unpack("<2i", get(struct.pack("<i", k),
                                              timeout=8))
        out[k] = (val, found)
    ch.close()
    return out


def spawn_backend() -> subprocess.Popen:
    """Start the backend child pinned to CPU (its real sockets never need
    the chip, and the chip belongs to one process). Shared by main() and
    the test so the spawn/teardown sequence cannot drift between them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--backend"],
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)))


def reap_backend(backend: subprocess.Popen) -> None:
    backend.terminate()
    try:
        backend.wait(timeout=10)
    except subprocess.TimeoutExpired:
        # a child wedged in jax import can ignore SIGTERM; never let the
        # reap raise out of a finally (it would mask the real failure and
        # leak the process + its bound UDP port)
        backend.kill()
        backend.wait()


def run_demo():
    """Spawn backend, run gateway, drive the third-party client; returns
    the observed results. Shared by main() and tests/test_grpc_gateway."""
    backend = spawn_backend()
    server = bridge = None
    try:
        server, bridge = make_gateway(schema_methods())
        server.start()
        return third_party_client()
    finally:
        if server is not None:
            server.stop(0)
        if bridge is not None:
            bridge.sock.close()
        reap_backend(backend)


def main():
    try:
        import grpc  # noqa: F401
    except ImportError:
        print(json.dumps({"metric": "grpc_gateway_demo",
                          "skipped": "grpcio not installed"}))
        return
    results = run_demo()
    assert results[0] == (100, 1), results
    assert results[1] == (101, 1), results
    assert results[3] == (0, 0), results
    print(json.dumps({
        "metric": "grpc_gateway_demo", "ok": True,
        "results": {str(k): v for k, v in results.items()},
        "note": ("vanilla grpc client -> HTTP/2 -> gateway -> "
                 "framework UDP wire -> RealRuntime service"),
    }))


if __name__ == "__main__":
    if "--backend" in sys.argv:
        backend_main(duration=60.0)
    else:
        main()
