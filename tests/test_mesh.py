"""Single-process sharded-engine tests on the virtual 8-device CPU mesh
(tests/conftest.py forces it): the seed batch shards with no per-step
communication, and the engine lowering knobs (int16 table columns,
scatter emission writes) compile and run under a mesh too — the
in-process complement of the driver's dryrun_multichip and the
2-process suite."""

import jax
import numpy as np

# back in tier-1 (r8 durations re-triage): the file was `slow` because it
# compiles many distinct step programs per run; with the shared
# ProgramCache + persistent compile cache live it measures ~15s warm /
# well inside tier-1's headroom cold (ROADMAP wall-clock item)

from madsim_tpu import Runtime, Scenario, SimConfig, NetConfig, ms
from madsim_tpu.core.types import sec
from madsim_tpu.models.pingpong import PingPong, state_spec
from madsim_tpu.parallel.mesh import seed_mesh, shard_batch
from madsim_tpu.utils.hashing import fingerprint

B = 64


def _rt(**cfg_kw):
    n = 3
    sc = Scenario()
    sc.at(ms(5)).kill_random()
    sc.at(ms(300)).restart_random()
    cfg = SimConfig(n_nodes=n, time_limit=sec(5),
                    net=NetConfig(packet_loss_rate=0.1), **cfg_kw)
    return Runtime(cfg, [PingPong(n, target=4, retry=ms(20))], state_spec(),
                   scenario=sc)


def _fps(rt, state):
    return np.asarray(jax.vmap(fingerprint)(state))


class TestShardedEngine:
    def test_sharded_run_bit_matches_unsharded(self):
        rt = _rt()
        plain, _ = rt.run(rt.init_batch(np.arange(B)), max_steps=4000)
        mesh = seed_mesh()
        assert mesh.devices.size >= 8          # conftest's virtual mesh
        sharded = shard_batch(rt.init_batch(np.arange(B)), mesh)
        sharded, _ = rt.run(sharded, max_steps=4000)
        assert bool(sharded.halted.all())
        np.testing.assert_array_equal(_fps(rt, plain), _fps(rt, sharded))

    def test_int16_columns_shard(self):
        # the narrow-dtype state shards and stays bit-identical to the
        # unsharded int32 run
        rt32 = _rt()
        plain, _ = rt32.run(rt32.init_batch(np.arange(B)), max_steps=4000)
        rt16 = _rt(table_dtype="int16")
        sharded = shard_batch(rt16.init_batch(np.arange(B)), seed_mesh())
        sharded, _ = rt16.run(sharded, max_steps=4000)
        assert bool(sharded.halted.all())
        np.testing.assert_array_equal(_fps(rt32, plain),
                                      _fps(rt16, sharded))

    def test_scatter_emission_shards(self):
        # the scatter emission lowering partitions along the seed axis
        # and stays bit-identical to the one-hot run under the mesh
        rt_oh = _rt(emission_write="onehot")
        plain, _ = rt_oh.run(rt_oh.init_batch(np.arange(B)), max_steps=4000)
        rt_sc = _rt(emission_write="scatter")
        sharded = shard_batch(rt_sc.init_batch(np.arange(B)), seed_mesh())
        state, _ = rt_sc.run(sharded, max_steps=4000)
        assert bool(state.halted.all())
        assert not bool(state.crashed.any())
        np.testing.assert_array_equal(_fps(rt_oh, plain),
                                      _fps(rt_sc, state))

    def test_compacting_keeps_the_mesh(self):
        # run_compacting re-packs lanes through the host; a sharded batch
        # must come back on its mesh (not on device 0), bit-identical
        import io
        from madsim_tpu.obs import JsonlObserver
        rt = _rt()
        mesh = seed_mesh(jax.devices()[:4])
        plain, _ = rt.run(rt.init_batch(np.arange(B)), 4000, chunk=16)
        obs = JsonlObserver(io.StringIO())
        state = rt.run_compacting(
            shard_batch(rt.init_batch(np.arange(B)), mesh), 4000, chunk=16,
            compact_when=0.3, min_batch=8, observer=obs)
        assert any(r["kind"] == "compact" for r in obs.records)
        assert len(state.now.sharding.device_set) == 4
        np.testing.assert_array_equal(_fps(rt, plain), _fps(rt, state))
