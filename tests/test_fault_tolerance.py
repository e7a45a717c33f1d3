"""Fault-tolerance behaviours: straggler detection, preemption checkpoint,
elastic restore across different mesh topologies (subprocess: own device
count)."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.train.loop import StepStats


def test_straggler_detection_flags_slow_steps():
    stats = StepStats()
    flagged = []
    for step in range(20):
        dt = 1.0 if step != 15 else 5.0       # one 5x straggler
        if stats.record(step, dt, factor=3.0):
            flagged.append(step)
    assert flagged == [15]
    assert stats.stragglers[0][0] == 15


def test_straggler_needs_history():
    stats = StepStats()
    # first few steps never flag (no stable median yet)
    assert not stats.record(0, 100.0, factor=3.0)


@pytest.mark.slow
def test_elastic_restore_across_topologies(tmp_path):
    """Save on a (2,2) mesh, restore on a (4,1) mesh — different shard
    layout, same logical arrays.  Runs in subprocesses so each side owns
    its XLA device count."""
    script_save = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                           NamedSharding(mesh, P("data", "model")))
        cm = CheckpointManager({str(tmp_path)!r})
        cm.save(5, {{"w": w}})
        print("SAVED")
    """)
    script_load = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cm = CheckpointManager({str(tmp_path)!r})
        step, state, _ = cm.restore({{"w": jnp.zeros((8, 8), jnp.float32)}},
                                    mesh=mesh,
                                    specs={{"w": P("data", "model")}})
        assert step == 5
        w = state["w"]
        assert len(w.sharding.device_set) == 4
        np.testing.assert_array_equal(
            np.asarray(w), np.arange(64, dtype=np.float32).reshape(8, 8))
        print("RESTORED-ELASTIC")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    for script, marker in [(script_save, "SAVED"),
                           (script_load, "RESTORED-ELASTIC")]:
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=300,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert marker in out.stdout, out.stderr[-2000:]


def test_preemption_checkpoint(tmp_path):
    """SIGTERM-equivalent: the trainer's preempt flag forces a checkpoint."""
    from repro.configs import get_config
    from repro.data.tokens import TokenPipeline, TokenPipelineConfig
    from repro.models.params import init_params
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig
    from repro.train.loop import Trainer, TrainLoopConfig

    cfg = get_config("llama3.2-1b").reduced()
    pipe = TokenPipeline(TokenPipelineConfig(vocab=cfg.vocab, seq_len=16,
                                             global_batch=2, seed=0))
    tr = Trainer(cfg, TrainLoopConfig(total_steps=50, ckpt_every=100,
                                      optimizer=AdamWConfig(lr=1e-3)),
                 pipe, str(tmp_path))
    params = init_params(jax.random.PRNGKey(0), cfg)

    # trip the preemption flag after the second step via the log hook
    calls = []

    def log(msg):
        calls.append(msg)

    orig_record = tr.stats.record

    def record_and_preempt(step, dt, factor):
        if step >= 1:
            tr._preempted = True
        return orig_record(step, dt, factor)

    tr.stats.record = record_and_preempt
    _, _, result = tr.run(params, adamw.init(params), log=log)
    assert result["last_step"] < 50                  # stopped early
    assert tr.ckpt.latest_step() == result["last_step"]
    _, _, extra = tr.ckpt.restore(
        {"params": params, "opt": adamw.init(params)})
    assert extra.get("preempted") is True
