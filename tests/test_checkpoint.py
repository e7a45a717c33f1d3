"""Checkpoint manager: roundtrip, retention, atomicity, validation,
corruption fallback, elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointCorruptError, CheckpointManager


def _state(seed=0):
    r = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(r.normal(0, 1, (8, 4)), jnp.float32),
                       "b": jnp.asarray(r.normal(0, 1, (4,)), jnp.bfloat16)},
            "opt": {"mu": jnp.zeros((8, 4)), "step": jnp.int32(7)}}


def test_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    state = _state()
    cm.save(10, state, extra={"loss": 1.25})
    step, restored, extra = cm.restore(jax.tree.map(jnp.zeros_like, state))
    assert step == 10 and extra["loss"] == 1.25
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_retention_and_latest(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        cm.save(s, _state(s))
    assert cm.all_steps() == [3, 4]
    assert cm.latest_step() == 4


def test_atomicity_no_tmp_left(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(5, _state())
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


def test_background_save(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, _state(), background=True)
    cm.wait()
    assert cm.latest_step() == 1


def test_structure_mismatch_rejected(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, _state())
    bad = {"params": {"w": jnp.zeros((8, 4))}}   # missing leaves
    with pytest.raises(ValueError, match="structure mismatch"):
        cm.restore(bad)


def test_truncated_checkpoint_detected_and_previous_loaded(tmp_path):
    """A snapshot truncated mid-write (SIGKILL during save) fails its
    checksum; restore() transparently falls back to the previous one."""
    cm = CheckpointManager(str(tmp_path), keep=3)
    cm.save(1, _state(1), extra={"epoch": 1})
    cm.save(2, _state(2), extra={"epoch": 2})
    leaves = os.path.join(tmp_path, "step_2", "leaves.npz")
    payload = open(leaves, "rb").read()
    with open(leaves, "wb") as f:
        f.write(payload[: len(payload) // 2])          # torn write
    assert not cm.validate(2) and cm.validate(1)
    assert cm.latest_valid_step() == 1
    step, restored, extra = cm.restore(
        jax.tree.map(jnp.zeros_like, _state()))
    assert step == 1 and extra["epoch"] == 1
    want = _state(1)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # asking for the torn snapshot explicitly is an error, not garbage data
    with pytest.raises(CheckpointCorruptError):
        cm.restore(jax.tree.map(jnp.zeros_like, _state()), step=2)


def test_bitflip_detected(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, _state())
    leaves = os.path.join(tmp_path, "step_1", "leaves.npz")
    payload = bytearray(open(leaves, "rb").read())
    payload[len(payload) // 2] ^= 0xFF
    open(leaves, "wb").write(bytes(payload))
    assert not cm.validate(1)
    with pytest.raises(FileNotFoundError, match="no valid checkpoints"):
        cm.restore(jax.tree.map(jnp.zeros_like, _state()))


def test_numpy_restore_preserves_wide_dtypes(tmp_path):
    """to_device=False must keep int64/float64 exactly (jnp would narrow)."""
    cm = CheckpointManager(str(tmp_path))
    state = {"pop": np.arange(12, dtype=np.int64).reshape(3, 4),
             "F": np.linspace(0, 1, 6, dtype=np.float64).reshape(3, 2)}
    cm.save(1, state)
    _, restored, _ = cm.restore({"pop": np.zeros((3, 4), np.int64),
                                 "F": np.zeros((3, 2), np.float64)},
                                to_device=False)
    assert restored["pop"].dtype == np.int64
    assert restored["F"].dtype == np.float64
    np.testing.assert_array_equal(restored["pop"], state["pop"])
    np.testing.assert_array_equal(restored["F"], state["F"])


def test_elastic_restore_to_mesh(tmp_path):
    """Restore re-device_puts with the current (1-device) mesh sharding —
    the same code path reshards onto any topology."""
    from jax.sharding import AxisType, PartitionSpec as P

    cm = CheckpointManager(str(tmp_path))
    state = _state()
    cm.save(3, state)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    specs = {"params": {"w": P("data", "model"), "b": P(None)},
             "opt": {"mu": P("data", None), "step": P()}}
    step, restored, _ = cm.restore(jax.tree.map(jnp.zeros_like, state),
                                   mesh=mesh, specs=specs)
    assert step == 3
    w = restored["params"]["w"]
    assert hasattr(w, "sharding")
    np.testing.assert_array_equal(np.asarray(w), np.asarray(state["params"]["w"]))
