"""CPU fixtures for the benchmark's own tests.

The harness runs here at each configuration's small cut, which its
architecture module states (``bench/arch/<arch>.py``, ``SMALL``), from a
copy of ``bench/`` whose configuration files state that cut and whose mixes
run at a rate the host CPU serves (``bench_root``), and the program serves
the sizes that the file it runs states (``small_program``).  JAX's
persistent compile cache stays off.
"""
import dataclasses
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pytest  # noqa: E402

from bench import arch  # noqa: E402


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    """``enable_compile_cache`` takes a set ``JAX_COMPILATION_CACHE_DIR``
    as JAX's own and configures nothing, so the cache stays off; the
    harness's zero minimum compile time is put back afterwards."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


# the queue depth every tier gets in the CPU tests
DEPTH = 256
# limits at the small width, from CPU runs of both cells there: bf16
# serving reads bias 0.0015-0.0017 and a widest gap of 0.0065-0.0067, the
# int8 control bias 0.0049-0.0051 (gap 0.0085), the planted faults a
# widest gap of 0.31-1.29; the chip's limits are PERF.md's
SMALL_LIMITS = {"bias": 0.003, "max_l2_gap": 0.05}


def small_cut(cfg: dict) -> dict:
    """The configuration at its architecture's small cut."""
    return dict(cfg, **arch.of(cfg).SMALL)


def program_config(cfg: dict):
    """The program's configuration of ``cfg["model"]`` at the sizes ``cfg``
    states: every whole number among the architecture's ``program_fields``
    set to the file's (``resolved_<x>`` through its field ``<x>``)."""
    from repro.configs import get_config

    pcfg = get_config(cfg["model"])
    names = {f.name for f in dataclasses.fields(pcfg)}
    sizes = {}
    for attr, want in arch.of(cfg).program_fields(cfg).items():
        field = attr.removeprefix("resolved_")
        if type(want) is int and field in names:
            sizes[field] = want
    return pcfg.replace(**sizes)


@pytest.fixture
def small_program(monkeypatch):
    """``bench.run.build`` builds the program at the sizes of the
    configuration it is given, with a fixed depth per tier: an Eq. 12
    sweep timed on a loaded test host can refuse its own fit, which says
    nothing about the harness."""
    from bench import run
    from repro.core.estimator import fit_latency
    from repro.launch import serve

    def calibrate(name, profile, slo, points):
        profile(points[0])           # runs the tier once, as a sweep would
        return DEPTH, fit_latency([1, DEPTH], [0.01, slo])

    build = run.build

    def small_build(cfg, seed):
        with monkeypatch.context() as m:
            m.setattr(serve, "get_config", lambda name: program_config(cfg))
            return build(cfg, seed)

    monkeypatch.setattr(run, "build", small_build)
    monkeypatch.setattr(serve, "calibrate", calibrate)


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


@pytest.fixture
def bench_root(tmp_path):
    """A checkout holding ``BENCHMARK.json`` and a copy of ``bench/`` at
    each configuration's small cut and the small limits, with short
    warm-ups and a low open-loop rate."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for f in (root / "bench" / "configs").glob("*.json"):
        cfg = small_cut(json.loads(f.read_text()))
        write_json(f, dict(cfg, limits=SMALL_LIMITS))
    for f in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        mix["warmup_s"] = 0.5
        if mix["loop"] == "open":
            mix["rate_qps"] = 200
        write_json(f, mix)
    return root
