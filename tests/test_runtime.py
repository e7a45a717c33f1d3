"""Runtime policy: compile-cache placement, one process per chip, and the
interpret-only-on-CPU rule of the Pallas circuit kernel.

The platform is steered inside each test by replacing
`jax.default_backend`, so the TPU branches run here without a chip."""
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import runtime
from repro.core import circuits as C
from repro.kernels import pallas_circuit_sim as PS

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def on_platform(monkeypatch):
    def set_platform(name: str) -> None:
        monkeypatch.setattr(jax, "default_backend", lambda: name)
    return set_platform


# -- compile cache -------------------------------------------------------------
def test_compile_cache_env_wins(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.enable_compile_cache() == tmp_path
    assert updates == [], "JAX reads the env var itself; set nothing"


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    want = ROOT / ".jax_cache"
    assert runtime.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", str(want))]
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_only_from_entry_points():
    """Importing the package never turns the cache on: the helper is
    called under `if __name__ == "__main__"` guards only."""
    callers = [p for p in (ROOT / "src").rglob("*.py")
               if "enable_compile_cache()" in p.read_text()
               and p.name != "runtime.py"]
    for path in callers:
        text = path.read_text()
        main_block = text[text.index('if __name__ == "__main__":'):]
        assert text.count("enable_compile_cache()") == \
            main_block.count("enable_compile_cache()"), path
    assert {p.parent.name + "/" + p.name for p in callers} == {
        "serve/__main__.py", "evolve/__main__.py", "compile/export.py",
        "compile/zoo.py", "autopilot/__main__.py"}


# -- interpret mode only on the CPU --------------------------------------------
@pytest.mark.parametrize("platform,interpret",
                         (("cpu", True), ("tpu", False)))
def test_kernel_interprets_only_on_cpu(monkeypatch, on_platform, platform,
                                       interpret):
    seen = []
    monkeypatch.setattr(PS, "_fused_padded",
                        lambda *a, **kw: seen.append(kw["interpret"]))
    on_platform(platform)
    rng = np.random.default_rng(3)
    pop = C.random_netlist_population(rng, 4, 6, 2, 2)
    words = np.ones((4, 3), dtype=np.uint32)
    PS._run(pop.op, pop.in0, pop.in1, pop.outputs, words, 4, decode=True)
    assert seen == [interpret]


def test_kernel_refuses_other_platforms(on_platform):
    on_platform("gpu")
    rng = np.random.default_rng(3)
    pop = C.random_netlist_population(rng, 4, 6, 2, 2)
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        PS.fused_eval_uint(pop.op, pop.in0, pop.in1, pop.outputs,
                           np.ones((4, 3), dtype=np.uint32), 4)


def test_no_interpret_override_on_the_serving_path():
    """Interpret mode follows the platform alone: no entry point of the
    serving or evolve path takes an override."""
    import inspect

    from repro.compile.program import CircuitProgram
    from repro.kernels import dispatch as D

    for fn in (D.population_eval_uint, D.population_eval_pop,
               D.program_eval_words, D.fleet_eval_words,
               PS.fused_eval_uint, PS.simulate_population,
               PS.fleet_eval_words, PS.fleet_table, PS.fleet_walk):
        assert "interpret" not in inspect.signature(fn).parameters, fn
    assert "pallas_interpret" not in CircuitProgram.__dataclass_fields__


# -- one process per chip ------------------------------------------------------
@pytest.mark.parametrize("platform", ("cpu", "tpu"))
def test_island_executor_device_backend(on_platform, platform):
    from repro.evolve.config import CampaignConfig
    from repro.evolve.executor import IslandExecutor
    from repro.evolve.problems import ProblemSpec

    on_platform(platform)
    spec = ProblemSpec("synth", {"n_genes": 4, "domain": 3})
    cfg = CampaignConfig(n_islands=2, pop_size=4, n_epochs=1,
                         gens_per_epoch=1, eval_backend="swar", workers=2)
    if platform == "tpu":
        with pytest.raises(RuntimeError, match=r"workers=0 \(serial"):
            IslandExecutor(spec, cfg)
    else:
        IslandExecutor(spec, cfg).close()


def test_zoo_workers_refused_on_tpu(on_platform, tmp_path):
    from repro.compile.zoo import build_zoo, make_entries

    on_platform("tpu")
    entries = make_entries(["cardio"], ["base"])
    with pytest.raises(RuntimeError, match=r"workers=1 \(entries compile"):
        build_zoo(entries, tmp_path, workers=2)


# -- chip smoke ----------------------------------------------------------------
def test_chip_smoke_refuses_without_tpu(capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    assert chip_smoke.main(["--out-dir", str(tmp_path / "out")]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err


def test_chip_smoke_fails_alone(tmp_path):
    """A directory with chip_smoke.py and nothing else of the repo."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin"})
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
