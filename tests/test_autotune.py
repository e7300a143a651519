"""Autotune: deterministic calibration, profile persistence, block-plan export."""
import json
import types

import numpy as np
import pytest

from repro.core import autotune, decision as dec, plan_cache
from repro.core import hardware as hw
from repro.core.falcon_gemm import FalconConfig


@pytest.fixture(autouse=True)
def isolated_profiles(tmp_path, monkeypatch):
    """Point the profile dir at a tmpdir and undo registry side effects."""
    monkeypatch.setenv(hw.ENV_PROFILE_DIR, str(tmp_path))
    before = dict(hw._PROFILES)
    plan_cache.reset()
    yield tmp_path
    hw._PROFILES.clear()
    hw._PROFILES.update(before)
    plan_cache.reset()


def model_timer(fn, *args):
    """Deterministic 'clock': seconds as a pure function of operand sizes."""
    elems = sum(int(np.prod(a.shape)) for a in args)
    return 1e-9 * elems + 1e-6


def test_autotune_deterministic_with_injected_timer():
    kw = dict(base="cpu_host", backend="jnp", timer=model_timer, validate=True)
    r1 = autotune.autotune(**kw)
    r2 = autotune.autotune(**kw)
    assert r1.profile.to_dict() == r2.profile.to_dict()
    assert [p.as_dict() for p in r1.probes] == [p.as_dict() for p in r2.probes]
    assert r1.model_rel_err == r2.model_rel_err
    assert r1.profile.name == "cpu_host_autotuned"
    assert r1.profile.flops_mul > 0 and r1.profile.beta > 0
    assert 0 < r1.profile.lcma_gemm_efficiency <= 1.0


def test_autotune_deterministic_on_pallas_interpret_backend():
    """Same probes, same timer => bit-identical calibration through the
    Pallas interpret-mode pipeline (kernels run, clock is injected)."""
    kw = dict(base="cpu_host", backend="pallas_interpret",
              shapes=[(16, 16, 16), (32, 16, 32)], timer=model_timer,
              validate=True)
    r1 = autotune.autotune(**kw)
    r2 = autotune.autotune(**kw)
    assert r1.profile.to_dict() == r2.profile.to_dict()
    assert r1.model_rel_err == r2.model_rel_err
    assert len(r1.probes) == 2 and len(r1.model_rel_err) == 2


def test_autotune_real_timing_smoke():
    """Tiny real-clock run: sane, positive, registered."""
    rep = autotune.autotune(base="cpu_host", backend="jnp",
                            shapes=[(64, 64, 64)], reps=1, warmup=1,
                            validate=False)
    p = rep.profile
    assert np.isfinite([p.flops_mul, p.flops_add, p.beta]).all()
    assert p.flops_mul > 0 and p.beta > 0
    assert hw.get_profile(p.name) is p            # registered by name


def test_calibrated_profile_loads_from_disk_into_decide(tmp_path):
    rep = autotune.autotune(base="cpu_host", backend="jnp", timer=model_timer,
                            validate=False, name="testhost_autotuned")
    path = hw.save_profile(rep.profile)
    assert path == hw.profile_path("testhost_autotuned")
    # drop the in-memory registration: decide() must load the JSON
    hw._PROFILES.pop("testhost_autotuned")
    d = dec.decide(8192, 8192, 8192, "testhost_autotuned", "float32")
    assert d.gemm_seconds == pytest.approx(
        dec.gemm_time(8192, 8192, 8192, rep.profile, "float32"))
    # FalconConfig resolves the same way (serving config by name)
    assert FalconConfig(hardware="testhost_autotuned").profile.beta == \
        pytest.approx(rep.profile.beta)


def test_calibrate_writes_profile_json_with_metadata(tmp_path):
    rep, path = autotune.calibrate(base="cpu_host", backend="jnp",
                                   timer=model_timer, validate=True)
    doc = json.load(open(path))
    assert doc["name"] == rep.profile.name
    meta = doc["_metadata"]
    assert meta["backend"] == "jnp" and meta["scheme"] == "strassen"
    assert len(meta["probes"]) == len(rep.probes)
    assert "strassen" in meta["block_plans"]
    # profile round-trips ignoring metadata
    p2 = hw.load_profile(path, register=False)
    assert p2.to_dict() == rep.profile.to_dict()


def test_block_plans_fit_vmem_budget():
    from repro.core import algorithms as alg
    from repro.kernels import tuning
    for name in ("strassen", "laderman"):
        l = alg.get(name)
        bp = tuning.block_plans(l, 4096, 4096, 4096, dtype="float32")
        assert bp["fused_gemm_vmem_bytes"] <= bp["vmem_budget_bytes"]
        assert bp["combine_a_vmem_bytes"] <= bp["vmem_budget_bytes"]
        Mp, Kp, Np = bp["padded_shape"]
        assert Mp % l.m == 0 and Kp % l.k == 0 and Np % l.n == 0
    # High-rank schemes overflow VMEM through the (R, bx, bz) accumulator even
    # at the smallest block (paper §IV-C); the planner degrades to minimum
    # blocks and the export reports the honest over-budget footprint.
    s444 = tuning.block_plans(alg.get("s444"), 4096, 4096, 4096)
    strassen = tuning.block_plans(alg.get("strassen"), 4096, 4096, 4096)
    assert s444["fused_gemm"] <= strassen["fused_gemm"]   # degraded blocks
    assert s444["fused_gemm_vmem_bytes"] > 0


def test_tune_cli_end_to_end(tmp_path, capsys):
    from repro.tools import tune
    rc = tune.main(["--hardware", "cpu_host", "--backend", "jnp",
                    "--shape", "64,64,64", "--reps", "1",
                    "--name", "cli_autotuned"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "wrote" in out and "warmed plan cache" in out
    prof = hw.load_profile(hw.profile_path("cli_autotuned"), register=False)
    assert prof.name == "cli_autotuned" and prof.flops_mul > 0
    warmed = plan_cache.PlanCache(
        path=str(tmp_path / "cli_autotuned.plans.json"))
    assert len(warmed) > 0


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", "tpu_v5e"),
    ("cpu", "cpu", "tpu_v5e"),
])
def test_device_profile_follows_device_kind(platform, kind, want):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    assert hw.device_profile_name(dev) == want


def test_unlisted_tpu_kind_raises():
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 hypothetical")
    with pytest.raises(KeyError, match="no hardware profile"):
        hw.device_profile_name(dev)


def test_default_config_prices_the_attached_device_and_interprets_on_cpu():
    # the suite runs on the CPU: kernels interpret, and the default config
    # prices the analytic v5e profile the plans are made for
    assert hw.interpret_kernels()
    assert FalconConfig().profile is hw.get_profile("tpu_v5e")
