"""The harness finds everything by name, and refuses to run off the chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import arch, flops, run
from bench.conftest import small_cut, write_json

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 77


def test_every_name_in_the_benchmark_has_its_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        _, cell, cfg, mix = run.cell_spec(ROOT, w["name"])
        assert cfg["name"] == cell["config"]
        mod = arch.load(ROOT, cfg["arch"])
        for name in ("init_weights", "forward", "encoder_flops",
                     "program_fields", "seeded_init"):
            assert callable(getattr(mod, name))
        assert mod.PROGRAM_INIT and mod.SMALL
        assert cfg["limits"] and set(cfg["limits"]) <= {"max_l2_gap", "bias"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(ROOT, m["name"]))
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in bench["workloads"]}


def test_a_cell_config_mix_and_metric_added_as_files_run(bench_root,
                                                         small_program):
    """A later change adds a cell by adding files and entries only."""
    b = bench_root / "bench"
    cfg = json.loads((b / "configs" / "bge-large-zh-v1.5.json").read_text())
    write_json(b / "configs" / "toy-enc.json", dict(cfg, name="toy-enc"))
    write_json(b / "traffic" / "toy_burst.json", {
        "loop": "open", "rate_qps": 100, "tokens": {"dist": "zipf", "a": 1.3},
        "lengths": {"dist": "normal", "mean": 20, "sd": 4, "lo": 8,
                    "hi": 32},
        "bursts": {"every_s": 1.0, "len_s": 0.25, "mult": 2},
        "warmup_s": 0.5, "shape_seed": 5, "prewarm": ["CPU"]})
    (b / "metrics" / "p90_ms.py").write_text(
        "from bench.stats import percentile\n\n\n"
        "def read(run):\n"
        "    r = run.requests\n"
        "    ok = (r.due >= run.w0) & (r.due < run.w1) & (r.status == 1)\n"
        "    p = percentile(r.done[ok] - r.due[ok], 90)\n"
        "    return None if p is None else p * 1e3\n")
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-enc", "source": "x",
                             "file": "bench/configs/toy-enc.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.burst", "config": "toy-enc",
                               "traffic": "toy_burst", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "p90_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["toy.burst"]})
    (bench_root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = run.run_cell(bench_root, "toy.burst", SEED, 1.5, False,
                       require_tpu=False)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"setup_s", "p90_ms"}
    assert out["metrics"]["p90_ms"]["value"] > 0
    assert out["attempted"] > 100
    assert list(out)[-1] == "check"
    assert out["device"]["platform"] == "cpu"


# a stand-in architecture: prenorm_encoder's block with an operation count,
# a small cut and a program field of its own, and a reference that negates
# every answer
TOY_ARCH = '''from bench.arch import prenorm_encoder as base

PROGRAM_INIT = base.PROGRAM_INIT
SMALL = dict(base.SMALL, num_hidden_layers=3)
init_weights = base.init_weights
seeded_init = base.seeded_init


def forward(cfg):
    fwd = base.forward(cfg)
    return lambda weights, tokens, mask: -fwd(weights, tokens, mask)


def encoder_flops(length, cfg):
    return 7 * length


def program_fields(cfg):
    return dict(base.program_fields(cfg), rope_theta=cfg["rope_theta"])
'''


def test_an_architecture_added_as_files_brings_its_own_parts(
        bench_root, small_program, monkeypatch):
    """A configuration whose ``bench/arch/<arch>.py`` is new runs with that
    module's reference, operation count, program check and small cut, and
    nothing else under ``bench/`` changes."""
    b = bench_root / "bench"
    (b / "arch" / "toy_negated.py").write_text(TOY_ARCH)
    arch.load(bench_root, "toy_negated")
    bge = json.loads((b / "configs" / "bge-large-zh-v1.5.json").read_text())
    cfg = small_cut(dict(bge, name="toy-negated", arch="toy_negated",
                         rope_theta=0.0))
    assert cfg["num_hidden_layers"] == 3
    write_json(b / "configs" / "toy-negated.json", cfg)
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-negated", "source": "x",
                             "file": "bench/configs/toy-negated.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.ingest", "config": "toy-negated",
                               "traffic": "ingest", "chips": 1,
                               "why": "test"})
    (bench_root / "BENCHMARK.json").write_text(json.dumps(bench))

    toy = run.run_cell(bench_root, "toy.ingest", SEED, 1.0, False,
                       require_tpu=False)
    assert toy["correct"] is False
    assert toy["check"]["max_l2_gap"]["value"] > 1.9
    assert flops.batch_flops([10, 20], cfg) == 7 * 30
    ok = run.run_cell(bench_root, "bge.ingest", SEED, 1.0, False,
                      require_tpu=False)
    assert ok["correct"] is True, ok["check"]

    def window(*a, **k):
        pytest.fail("the window opened")

    monkeypatch.setattr(run, "Driver", window)
    with pytest.raises(RuntimeError, match="rope_theta"):
        run.run_cell(bench_root, "toy.ingest", SEED, 1.0, False,
                     require_tpu=False, config=dict(cfg, rope_theta=1e4))


def test_no_tpu_means_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "bge.query_steady", "--seed", str(SEED),
                  "--seconds", "1"])
    assert e.value.code == 2
    assert capsys.readouterr().out == ""


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bge.query_steady",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
