"""Drive `bench/run.py` without a chip: the chip check, the peaks table
and the trace reduction are stood in for, everything else is the run."""
import json

import jax

from harness import device as D
from harness import xplane as X

FAKE_TRACE = {"busy_s": 0.1, "window_s": 1.0, "n_devices": 1,
              "busy_s_per_device": [0.1], "device_ops": [["op", 0.1]],
              "idle_gaps": [["gap", 0.9]]}


def chipless(monkeypatch) -> None:
    monkeypatch.setattr(D, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(D, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(D, "peaks", lambda kind: {
        "hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12})
    monkeypatch.setattr(X, "find_xplane", lambda d: d)
    monkeypatch.setattr(X, "reduce_trace", lambda p, top=10: FAKE_TRACE)


def run_cell(capsys, workload: str, seed: int = 11, seconds: float = 1.0,
             trace: int = 0) -> dict:
    import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    return json.loads(out[-1])
