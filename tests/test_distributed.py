"""Multi-PROCESS smoke test: two jax.distributed processes on localhost
split a seed sweep and agree with the single-process run — the DCN-path
analog of the reference's multi-host deployments, runnable without
hardware (CPU backend, loopback coordinator)."""

import os
import subprocess
import sys

import numpy as np
import pytest

WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")

# distributed init MUST precede anything that initializes the XLA backend
# (including the flax import chain inside madsim_tpu)
pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()

sys.path.insert(0, {root!r})
from madsim_tpu.parallel.distributed import host_seed_slice, shard_global
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu import Runtime, SimConfig
from madsim_tpu.core.types import sec
import numpy as np
rt = Runtime(SimConfig(n_nodes=3, time_limit=sec(30)),
             [PingPong(3, target=5)], state_spec())
seeds = host_seed_slice(32)
state = shard_global(rt, seeds)
state, _ = rt.run(state, 4000, chunk=512)
# cross-process reduction over the sharded batch rides the collective path
total_acked = int(jax.jit(lambda s: s.node_state["acked"][:, 0].sum())(state))
halted = bool(jax.jit(lambda s: s.halted.all())(state))
print(f"RESULT pid={{pid}} local_seeds={{len(seeds)}} "
      f"total_acked={{total_acked}} halted={{halted}}", flush=True)
"""


WORKER2 = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")

pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id=pid)

sys.path.insert(0, {root!r})
import numpy as np
from madsim_tpu import Runtime, SimConfig, NetConfig
from madsim_tpu.core.types import sec
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.parallel.distributed import (host_seed_slice,
                                             run_compacting_sharded)
from madsim_tpu.utils.hashing import fingerprint

# loss spreads halting times so compaction actually fires
rt = Runtime(SimConfig(n_nodes=3, time_limit=sec(60),
                       net=NetConfig(packet_loss_rate=0.3)),
             [PingPong(3, target=5)], state_spec())
seeds = host_seed_slice(32)

# ground truth: this host's slice, no compaction
plain, _ = rt.run(rt.init_batch(seeds), 20_000, chunk=256)
fp_plain = np.asarray(jax.vmap(fingerprint)(plain))

# per-host compaction + global assembly (BASELINE config 4 at scale)
gstate = run_compacting_sharded(rt, seeds, 20_000, chunk=256,
                                compact_when=0.25, min_batch=4)
halted = bool(jax.jit(lambda s: s.halted.all())(gstate))

# fingerprints of the compacted local slice must match the plain run
# bit-for-bit (lane re-packing must be invisible to trajectory content)
comp_local = rt.run_compacting(rt.init_batch(seeds), 20_000, chunk=256,
                               compact_when=0.25, min_batch=4)
fp_comp = np.asarray(jax.vmap(fingerprint)(comp_local))
print(f"RESULT pid={{pid}} fp_match={{bool((fp_plain == fp_comp).all())}} "
      f"halted={{halted}}", flush=True)
"""


# this jaxlib's CPU backend may not implement cross-process collectives
# at all ("Multiprocess computations aren't implemented on the CPU
# backend") — an environment capability, not a code defect. The workers
# run to the first collective either way, so the marker in their output
# distinguishes "backend can't" (skip, precisely) from a real failure.
MULTIPROC_UNSUPPORTED = "Multiprocess computations aren't implemented"
_multiproc_broken = False    # memo: once one worker pair proves the
                             # backend can't, later tests skip instantly


def _run_two_workers(tmp_path, template, name):
    """Launch the two-process worker script on a loopback coordinator and
    return the RESULT lines; skip if the backend lacks multiprocess CPU
    support, fail on anything else."""
    import socket

    global _multiproc_broken
    if _multiproc_broken:
        pytest.skip("this jaxlib's CPU backend lacks multiprocess "
                    "collectives (established earlier in this session)")

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:  # ephemeral port: no CI collisions
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    f = tmp_path / name
    f.write_text(template.format(root=root, port=port))
    procs = [subprocess.Popen([sys.executable, str(f), str(i)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed worker timed out")
        outs.append(out)
    results = [l for o in outs for l in o.splitlines()
               if l.startswith("RESULT")]
    if len(results) != 2:
        if any(MULTIPROC_UNSUPPORTED in o for o in outs):
            _multiproc_broken = True
            pytest.skip("this jaxlib's CPU backend lacks multiprocess "
                        "collectives; the DCN path needs real multi-host "
                        "(or a jaxlib with CPU cross-process support)")
        pytest.fail(f"workers failed:\n{outs[0]}\n{outs[1]}")
    return results


class TestDistributed:
    def test_two_process_sweep(self, tmp_path):
        results = _run_two_workers(tmp_path, WORKER, "worker.py")
        # both processes see the same GLOBAL reduction over 32 seeds
        acked = [int(r.split("total_acked=")[1].split()[0]) for r in results]
        halted = [r.split("halted=")[1].strip() for r in results]
        assert acked[0] == acked[1] and acked[0] >= 32 * 5
        assert halted == ["True", "True"]

    def test_two_process_compacting_matches_plain(self, tmp_path):
        # VERDICT r2 next #5: compact-per-host-slice-then-reassemble. Each
        # process compacts its local slice; per-lane state fingerprints
        # must be bit-identical to the non-compacting run, and the
        # assembled global state must report all-halted.
        results = _run_two_workers(tmp_path, WORKER2, "worker2.py")
        for r in results:
            assert "fp_match=True" in r, r
            assert "halted=True" in r, r
