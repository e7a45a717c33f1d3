"""AOT compiles of the circuit kernels for a TPU v5e, without a chip.

The TPU compiler is installed alongside JAX, so the kernels of the main
path are compiled here for a described `v5e:2x2` topology at real
widths: the serving shape of the largest Table-2 classifier
(arrhythmia), the campaign's population shape, and fused fleet launches
of five and twenty tenants.  Interpret-mode tests cannot see what Mosaic refuses (unaligned
blocks, dynamic lane indexing, unsupported primitives); these can.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports
every test file.
"""
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import circuit_sim as CS
from repro.kernels import pallas_circuit_sim as PS

GOLDEN = Path(__file__).parent / "golden"
# arrhythmia: 274 ABC inputs, 16 classes -> a 4-bit argmax output plane
ARR_GATES = json.loads(
    (GOLDEN / "arrhythmia_report.json").read_text())["n_gates"]
ARR_INPUTS, ARR_OUT = 274, 4
SERVE_WORDS = 8          # one 256-reading serving batch, 32 per word

# name: (P, gates, inputs, outputs, word-plane shape, word tile)
PALLAS_CASES = {
    # one program over one serving batch (shared word plane)
    "serve_arrhythmia": (1, ARR_GATES, ARR_INPUTS, ARR_OUT,
                         (ARR_INPUTS, SERVE_WORDS), SERVE_WORDS),
    # a wide batch tiles the word axis in 128-lane blocks
    "serve_arrhythmia_tiled": (1, ARR_GATES, ARR_INPUTS, ARR_OUT,
                               (ARR_INPUTS, 512), 128),
    # TNN campaign objective: 32 genomes, each on its own plane (cardio:
    # 1,488 training rows -> 48 words), output popcount circuits
    "evolve_population": (32, 96, 16, 5, (32, 16, 48), 48),
    # five per-row planes at arrhythmia's gate budget (plus a zero gate)
    "fleet_5_tenants": (5, ARR_GATES + 1, ARR_INPUTS, ARR_OUT,
                        (5, ARR_INPUTS, SERVE_WORDS), SERVE_WORDS),
}

# the fused fleet walk at a manifest's largest launch shape: name ->
# (slots, table rows with the empty one); plans padded to arrhythmia's
# gate budget and inputs, one 256-reading plane a slot
FLEET_CASES = {
    "fleet_5_tenants": (5, 6),
    "fleet_20_tenants": (20, 21),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("decode", (True, False), ids=("decode", "words"))
@pytest.mark.parametrize("case", sorted(PALLAS_CASES))
def test_pallas_gate_walk_compiles_for_v5e(one_chip, case, decode):
    P, G, n_in, n_out, wshape, bw = PALLAS_CASES[case]
    compiled = PS._fused_padded.lower(
        _sds((P, 6, G), jnp.int32, one_chip),
        _sds((P, 1, n_out), jnp.int32, one_chip),
        _sds(wshape, jnp.uint32, one_chip),
        n_inputs=n_in, block_words=bw, decode=decode,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    Wp = wshape[-1]
    out_bytes = P * Wp * 4 * (32 if decode else n_out)
    assert mem.output_size_in_bytes >= out_bytes


@pytest.mark.parametrize("case", sorted(FLEET_CASES))
def test_fleet_walk_compiles_for_v5e(one_chip, case):
    T, N = FLEET_CASES[case]
    G = ARR_GATES + 1
    compiled = PS._fleet_walk.lower(
        _sds((T,), jnp.int32, one_chip), _sds((N,), jnp.int32, one_chip),
        _sds((N, 6, G), jnp.int32, one_chip),
        _sds((N, 1, ARR_OUT), jnp.int32, one_chip),
        _sds((T, ARR_INPUTS, SERVE_WORDS), jnp.uint32, one_chip),
        n_inputs=ARR_INPUTS, block_words=SERVE_WORDS,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        T * SERVE_WORDS * 32 * 4


def test_swar_scan_compiles_for_v5e_at_arrhythmia(one_chip):
    plan = _sds((1, ARR_GATES), jnp.int32, one_chip)
    compiled = CS.population_eval_uint.lower(
        plan, plan, plan, _sds((1, ARR_OUT), jnp.int32, one_chip),
        _sds((ARR_INPUTS, SERVE_WORDS), jnp.uint32, one_chip),
        n_inputs=ARR_INPUTS).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        SERVE_WORDS * 32 * 4
