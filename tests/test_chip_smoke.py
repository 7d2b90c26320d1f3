"""chip_smoke.py: its phases on the CPU at a reduced width, and its refusal
to report a result without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture
def reduced_qwen(monkeypatch):
    """qwen2.5-1.5b's head grouping (6 query heads per KV head) and bf16
    compute at a width the interpreted kernels run in seconds."""
    from repro.configs.registry import get_config
    from repro.launch import serve
    from repro.models import api

    full = serve.load_model

    def load_model(args):
        _, tok, _ = full(args)
        cfg = get_config("qwen2.5-1.5b").with_(
            n_layers=2, d_model=64, n_heads=12, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab_size=512)
        return cfg, tok, api.get_model(cfg)

    monkeypatch.setattr(serve, "load_model", load_model)


def test_phases_pass_on_reduced_width(reduced_qwen):
    """Every phase serves, drains its pool, hits the prefix cache, and its
    kernel path agrees with the XLA path within the stated tolerance."""
    report = chip_smoke.run(chip_smoke.SERVE_ARGV + [
        "--tasks", "2", "--budget", "2", "--max-tokens", "4"])
    assert set(report) == {name for name, *_ in chip_smoke.PHASES}
    for d in report.values():
        assert d["decode_tokens"] > 0
        assert d["prefix_hit_rate"] > 0
        assert d["max_abs_dlogit"] <= chip_smoke.REL_TOL * d["logit_scale"]


def test_phase_fails_when_kernel_path_disagrees(reduced_qwen, monkeypatch):
    """A kernel path whose logits drift past the tolerance fails the run
    instead of passing quietly."""
    real = chip_smoke.teacher_forced_logits
    calls = []

    def skewed(engine, prompts, forced):
        out = real(engine, prompts, forced)
        calls.append(1)
        return out * 2.0 if len(calls) == 1 else out   # the kernel path

    monkeypatch.setattr(chip_smoke, "teacher_forced_logits", skewed)
    with pytest.raises(RuntimeError, match="differs from the XLA path"):
        chip_smoke.run(chip_smoke.SERVE_ARGV + [
            "--tasks", "2", "--budget", "2", "--max-tokens", "2"],
            phases=chip_smoke.PHASES[:1])


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
