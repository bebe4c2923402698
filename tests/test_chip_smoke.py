"""chip_smoke.py off the chip: the script refuses to run without a TPU,
and each phase function passes at a tiny config on the CPU with the
Pallas kernels interpreted — so a chip call is never spent finding a
Python error. (What only the chip can say — Mosaic compiles, VMEM
limits, bf16 parity margins at full width — is chip_smoke.py's own job.)
"""
import os
import subprocess
import sys

import pytest

import paddle_tpu.ops.pallas.flash_attention as fa
import paddle_tpu.ops.pallas.fused_ops as fk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def interpreted(monkeypatch):
    """Kernels through the Pallas interpreter, ONE (multi-tile) candidate
    per list instead of three to five: the sweep over every candidate is
    what the chip run is for, and tier-1 has no seconds to spare."""
    monkeypatch.setattr(fa, "INTERPRET", True)
    monkeypatch.setattr(fk, "INTERPRET", True)
    monkeypatch.setattr(fa, "FWD_TILE_CANDIDATES", [(64, 64)])
    monkeypatch.setattr(fa, "BWD_TILE_CANDIDATES", [(64, 128)])
    monkeypatch.setattr(fk, "NORM_ROW_CANDIDATES", [32])
    monkeypatch.setattr(fk, "MATMUL_TILE_CANDIDATES", [(32, 128)])


def test_script_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""        # no result line off the chip


def test_device_phase_names_the_platform_it_requires():
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.phase_device()
    dev = chip_smoke.phase_device(require_platform="cpu")
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert set(dev["versions"]) == {"jax", "jaxlib", "libtpu"}


def test_last_line_is_the_verdict_and_nothing_else(monkeypatch, capsys):
    """The driver's check reads the LAST stdout line: exactly ok + device
    {platform, kind, count}; the per-phase report is the line before."""
    import functools
    import json
    monkeypatch.setattr(chip_smoke, "phase_device", functools.partial(
        chip_smoke.phase_device, require_platform="cpu"))
    monkeypatch.setattr(chip_smoke, "phase_kernels", lambda: {"n": 1})
    monkeypatch.setattr(chip_smoke, "phase_train", lambda: {"n": 2})

    def broken():
        raise ValueError("serve broke")

    monkeypatch.setattr(chip_smoke, "phase_serve", broken)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-unused")
    assert chip_smoke.main() == 1
    report, last = map(json.loads, capsys.readouterr().out.splitlines()[-2:])
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["count"], int)
    assert report["phases"]["serve"]["ok"] is False
    assert report["phases"]["train"]["ok"] is True
    assert set(report["versions"]) == {"jax", "jaxlib", "libtpu"}


def test_kernels_phase_tiny(interpreted):
    out = chip_smoke.phase_kernels(
        flash_shapes=((1, 128, 32),),
        fused_shape=dict(rows=32, hidden=128, ffn=256, head_dim=32, seq=16))
    names = " ".join(out["max_rel_err"])
    for site in ("flash_fwd", "flash_dq_dkdv", "fused_residual_norm",
                 "fused_bias_act", "fused_matmul[", "fused_matmul_rope",
                 "flash_fwd[S=128,d=32,window=64](32,32)",
                 "flash_dq_dkdv[S=128,d=32,window=64](2048,2048)"):
        assert site in names, names
    # the windowed kernels: forward and backward at two tiles
    assert out["kernels_checked"] == len(out["max_rel_err"]) == 13


def test_kernels_phase_names_every_broken_kernel(interpreted, monkeypatch):
    monkeypatch.setattr(fk, "_act_kernel", lambda y, act: y)   # wrong math
    with pytest.raises(RuntimeError) as e:
        chip_smoke.phase_kernels(
            flash_shapes=(),
            fused_shape=dict(rows=32, hidden=128, ffn=256, head_dim=32,
                             seq=16))
    msg = str(e.value)
    assert "fused_bias_act[gelu](32)" in msg and "fused_matmul[" in msg
    assert "fused_residual_norm" not in msg     # the healthy ones pass


def test_train_phase_tiny(monkeypatch):
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models import GPTConfig
    # a file that ran before this one in the worker may have left its mesh
    monkeypatch.setattr(mesh_mod, "_global_mesh", None)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=16, recompute=True)
    # the suite's 8 virtual devices: the Engine's default dp mesh spans
    # them, which is the multi-chip branch of the phase
    out = chip_smoke.phase_train(cfg, batch=8, steps=3)
    assert out["mesh"] == {"dp": 8}
    assert out["step_compiles"] == 1 and len(out["losses"]) == 3
    assert out["losses"][-1] < out["losses"][0]
    # on the CPU the model's attention is the XLA composite: no Mosaic
    # call is demanded here, and none is claimed
    assert out["mosaic_custom_calls"] == 0


def test_serve_phase_tiny():
    from paddle_tpu.models import LlamaConfig
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_layers=1, num_heads=2, max_seq_len=64,
                      use_flash_attention=False)
    # bf16 weights, the chip's serving dtype (exact f32 engine-vs-generate
    # parity is test_serving.py's): generate() must run — its KV cache
    # follows the weights' dtype — and both decode paths stay greedy
    # under the reference forward up to bf16 rounding
    out = chip_smoke.phase_serve(cfg, n_requests=3, prompt_range=(3, 12),
                                 new_tokens=6, max_batch=2, block_size=4)
    assert out["finished"] == 3 and out["tick_failures"] == 0
    assert out["lifecycle"] == "READY"
    assert max(out["greedy_margin_vs_reference"].values()) \
        <= out["greedy_margin_allowed"]


def test_run_phases_reports_every_phase_after_a_failure():
    def boom():
        raise ValueError("broken phase")

    report = chip_smoke.run_phases([("a", boom), ("b", lambda: {"x": 1})])
    assert report["a"]["ok"] is False and "broken phase" in report["a"]["error"]
    assert report["b"]["ok"] is True and report["b"]["x"] == 1
    assert all({"wall_s", "compile_s"} <= set(r) for r in report.values())


# ------------------------------------------- no fallback that hides the device
def test_tpu_place_without_a_tpu_is_an_error():
    import paddle_tpu as paddle
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        paddle.TPUPlace(0).jax_device()


def test_unknown_device_kind_has_no_peak():
    from paddle_tpu.observability import perf

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    class Unknown:
        platform, device_kind = "tpu", "TPU v99"

    assert perf.chip_peak_flops(Chip()) == 197e12
    assert perf.chip_peak_bw(Chip()) == 819e9
    assert perf.chip_hbm_bytes(Chip()) == 16e9
    for fn in (perf.chip_peak_flops, perf.chip_peak_bw, perf.chip_hbm_bytes):
        with pytest.raises(KeyError, match="TPU v99"):
            fn(Unknown())
    # the CPU keeps nominal figures, under names that say so
    assert perf.chip_peak_flops() == perf.CPU_NOMINAL_FLOPS


def test_launcher_refuses_many_workers_on_a_tpu_host(monkeypatch):
    from paddle_tpu.distributed.launch import main as launch

    monkeypatch.setattr(launch.glob, "glob",
                        lambda pat: ["/dev/accel0"] if "accel" in pat else [])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")      # workers pinned off it
    assert not launch._workers_would_claim_tpu()
    monkeypatch.delenv("JAX_PLATFORMS")
    assert launch._workers_would_claim_tpu()
    with pytest.raises(SystemExit, match="one process at a time"):
        launch.launch(["--nproc_per_node", "2", "train.py"])
