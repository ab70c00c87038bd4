"""chip_smoke.py at a tiny size on the CPU, so the chip script cannot rot
between chip runs: every phase runs end to end with Pallas in interpret
mode, and the script refuses to run its phases without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys

import jax  # noqa: F401  (initialized before main() applies its env)
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def tiny_sizes(mod):
    return mod.Sizes(fit_n=96, fit_p=24, fit_k=3, fit_iters=3,
                     kernel_iters=2, stream_n=3000, stream_p=8,
                     stream_chunk=1024, requests=40, strata=3, grid=16,
                     deep_steps=2, deep_batch=8, deep_seq=16, deep_k=2,
                     mc_n=4096, mc_p=8, mc_batch=512, ssd_batch=1,
                     ssd_seq=40, ssd_shapes=((4, 64, 1, 128, 16),
                                             (4, 64, 2, 128, 16)))


def test_single_chip_phases_pass_on_cpu(chip_smoke, capsys):
    from repro.survival import deep

    cfg = deep.model_config(deep.DeepSurvivalConfig())
    phases = [("fit", chip_smoke.phase_fit),
              ("stream", chip_smoke.phase_stream),
              ("serve", chip_smoke.phase_serve),
              ("deep", chip_smoke.phase_deep),
              ("ssd", chip_smoke.phase_ssd)]
    # on the CPU the stream resolves to the jnp scan, the engine to the
    # jnp baseline gather and the fit's sweeps to the jnp sweep (traced
    # onto the kernel path all the same); the other kernels run in
    # interpret mode
    expected = {"fit": ("cd_sweep",), "serve": ("survival_curves",),
                "deep": ("survival_curves",)}
    ok = chip_smoke.run_phases(phases, tiny_sizes(chip_smoke),
                               chip_smoke.CompileClock(), expected,
                               deep={"cfg": cfg})
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["phase"] for ln in lines] == [p for p, _ in phases]
    assert ok, [ln for ln in lines if not ln["ok"]]
    fit = lines[0]
    assert fit["monotonicity_violations"] == 0
    assert fit["kernel_vs_jnp_beta_max_abs_diff"] < 1e-4
    serve = lines[2]
    for key in ("single_stratum", "strata_3"):
        assert serve[key]["requests"] == 40
        assert serve[key]["errors"] == serve[key]["engine_failures"] == 0
    # the SSD kernel (interpret mode here) against the jnp body
    ssd = lines[4]
    gaps = [g for key in ("H4_hd64_G1_N128_Q16", "H4_hd64_G2_N128_Q16")
            for g in ssd[key]["max_rel_gap"].values()]
    assert len(gaps) == 14 and max(gaps) < 2.5e-2, ssd


def test_refuses_to_run_without_tpu(chip_smoke, capsys, monkeypatch):
    # runtime.apply() writes its defaults into a copy of the environment
    monkeypatch.setattr(os, "environ", dict(os.environ))
    assert chip_smoke.main([]) == 2
    assert capsys.readouterr().out == ""


MULTICHIP = r"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
sz = cs.Sizes(mc_n=4096, mc_p=8, mc_batch=512, grid=16)
ok = cs.run_phases([("multichip", cs.phase_multichip)], sz,
                   cs.CompileClock())
sys.exit(0 if ok else 1)
"""


def test_multichip_phase_passes_on_four_host_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", MULTICHIP, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["ok"] and line["devices"] == 4
    assert line["sharded_input_devices"] == ["0", "1", "2", "3"]
    assert line["scoring_bitwise_equal"]
