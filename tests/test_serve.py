"""The serve entry point: which devices each tier lands on, how each tier's
depth is calibrated, and the CLI's exit status.

The process under test has only CPU devices, so the tests steer
``probe_jax_devices`` and the backend class inside the test: a fake
accelerator device stands in for a TPU chip, and a fake backend records the
devices it was given and answers with a service time that grows per query.
"""
import dataclasses
import sys
import time

import jax
import numpy as np
import pytest

from repro.core.device_detector import DeviceInventory
from repro.core.routing import CPU, NPU, TierSpec
from repro.core.windve import Backend, WindVE
from repro.launch import serve

# a short SLO keeps the calibrated depths, and so the probes, small
SLO = 0.2


@dataclasses.dataclass(frozen=True)
class FakeTPU:
    id: int = 0
    platform: str = "tpu"
    device_kind: str = "TPU v5 lite"


class FakeBackend(Backend):
    """Records its placement; serves in 2 ms + a per-query slope that is
    steeper on the CPU, like the real tiers."""

    def __init__(self, cfg, params, *, max_tokens, devices, min_seq_bucket):
        self.devices = list(devices)
        self.platform = self.devices[0].platform
        self.device_count = len(self.devices)
        self.min_batch_bucket = 1
        self.dtype = "fp32"
        self.name = f"fake@{self.platform}"
        self.traces = 0
        self.prewarmed = []
        self.batches = []
        self.per_query_s = 1e-2 if self.platform == "cpu" else 1e-3

    def warm_grid(self, max_batch):
        return [(max_batch, 96)]

    def prewarm(self, grid):
        self.prewarmed.extend(grid)
        return len(grid)

    def embed_batch(self, queries):
        self.batches.append(len(queries))
        time.sleep(2e-3 + self.per_query_s * len(queries))
        return [np.full(4, 0.5, np.float32) for _ in queries]


@pytest.fixture
def fake_host(monkeypatch):
    """A host with one (fake) TPU chip beside the real CPU device."""
    cpu = jax.devices("cpu")[0]

    def use(npu_devices):
        monkeypatch.setattr(serve, "probe_jax_devices", lambda: DeviceInventory(
            npus=len(npu_devices), cpus=1, npu_devices=tuple(npu_devices),
            cpu_devices=(cpu,)))
        monkeypatch.setattr(serve, "ShardedEmbedderBackend", FakeBackend)

    return use


def test_build_engine_places_tiers_by_platform(fake_host, capsys):
    fake_host([FakeTPU()])
    engine, cfg = serve.build_engine(smoke=True, slo=SLO, prewarm=True)
    try:
        tiers = {t.name: t for t in engine.qm.tiers}
        assert set(tiers) == {NPU, CPU}
        npu, cpu = tiers[NPU].backend, tiers[CPU].backend
        assert {d.platform for d in npu.devices} == {"tpu"}
        assert {d.platform for d in cpu.devices} == {"cpu"}
        # each tier batches (and prewarms) at its own platform's width
        assert tiers[NPU].max_batch == serve.ACCEL_MAX_BATCH
        assert tiers[CPU].max_batch == serve.CPU_MAX_BATCH
        assert npu.prewarmed == [(serve.ACCEL_MAX_BATCH, 96)]
        assert cpu.prewarmed == [(serve.CPU_MAX_BATCH, 96)]
        # every probe batch stays within the tier's batch width, so the
        # calibration only runs prewarmed shapes
        assert max(npu.batches) == serve.ACCEL_MAX_BATCH
        assert max(cpu.batches) == serve.CPU_MAX_BATCH
        # each depth comes from its own backend's sweep: the slower CPU
        # tier calibrates shallower
        assert 0 < tiers[CPU].depth < tiers[NPU].depth
    finally:
        engine.shutdown()
    assert "main=npu aux=cpu heter=True" in capsys.readouterr().out


def test_cpu_only_host_serves_from_the_cpu_alone(fake_host, capsys):
    fake_host([])
    engine, _ = serve.build_engine(smoke=True, slo=SLO)
    try:
        (tier,) = engine.qm.tiers
        assert tier.name == CPU
        assert {d.platform for d in tier.backend.devices} == {"cpu"}
    finally:
        engine.shutdown()
    assert "main=cpu aux=none heter=False" in capsys.readouterr().out


def test_named_modeled_accelerator_is_used_only_when_named(fake_host):
    from repro.core.windve import ModeledBackend

    fake_host([])
    engine, _ = serve.build_engine(smoke=True, slo=SLO,
                                   npu_model="tesla-v100/bge")
    try:
        tiers = {t.name: t for t in engine.qm.tiers}
        assert isinstance(tiers[NPU].backend, ModeledBackend)
        assert {d.platform for d in tiers[CPU].backend.devices} == {"cpu"}
    finally:
        engine.shutdown()


def test_probe_points_grow_across_batches():
    assert serve.probe_points(64) == (16, 32, 64, 128)
    assert serve.probe_points(8) == (2, 4, 8, 16)
    # a mesh-floored backend never probes below its smallest bucket
    assert serve.probe_points(8, floor=4) == (4, 4, 8, 16)


def test_flat_fit_stops_calibration():
    """A profile that does not grow with the queue has no Eq. 12 slope:
    the estimator's unbounded sentinel must stop the run, not be served."""
    with pytest.raises(RuntimeError, match="flat"):
        serve.calibrate(NPU, lambda c: 0.01, 1.0, (16, 32, 64, 128))
    depth, fit = serve.calibrate(NPU, lambda c: 0.01 + 1e-3 * c, 1.0,
                                 (16, 32, 64, 128))
    assert depth == 990 and fit.alpha > 0


def test_calibration_reprobes_past_the_probed_range():
    """Service time that bends upward above the probe points: the line
    through 16-128 reads a depth of 990, where the queue really takes
    1.7 s.  Calibration probes again near each extrapolated depth, so the
    depth it returns lies inside the measured range and meets the SLO."""
    probed = []

    def profile(c):
        probed.append(c)
        return 0.01 + 1e-3 * c + 2e-6 * max(0, c - 128) ** 2

    depth, _ = serve.calibrate(NPU, profile, 1.0, (16, 32, 64, 128))
    assert 128 < depth <= max(probed)
    assert profile(990) > 1.7
    assert profile(depth) <= 1.0
    # each re-probe reaches at most 4x the largest point before it
    assert probed[:5] == [16, 32, 64, 128, 512]


def test_calibration_stops_when_the_depth_stays_out_of_range():
    """A profile whose fit keeps running ahead of every probe is refused
    after a bounded number of re-probes instead of being served."""
    probed = []

    def profile(c):
        probed.append(c)
        return 0.5 * (c / max(probed)) ** 4 if c > 128 else 1e-4 * c

    with pytest.raises(RuntimeError, match="past the largest probe point"):
        serve.calibrate(NPU, profile, 1.0, (16, 32, 64, 128))
    assert len(probed) == 4 + serve.REPROBE_ROUNDS


class FailingBackend(Backend):
    name = "failing"

    def embed_batch(self, queries):
        raise RuntimeError("device lost")


def test_cli_exits_nonzero_when_an_accepted_query_fails(monkeypatch):
    from repro.configs import get_config

    cfg = get_config("bge-large-zh-v1.5").smoke()
    monkeypatch.setattr(serve, "build_engine", lambda *a, **kw: (
        WindVE(tiers=[TierSpec(CPU, 8, backend=FailingBackend())]), cfg))
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(sys, "argv", ["serve", "--smoke", "--queries", "3"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code not in (0, None)
    assert "3 accepted queries failed" in str(exc.value.code)
