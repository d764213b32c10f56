"""`chip_smoke.py` on the CPU: without a TPU it refuses to run, and its
phases, rehearsed at tiny sizes (reduced config, kernels in interpret mode,
four virtual devices for the four-chip paths), pass their own checks. The
chip runs the same phases at full width."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SERVE = ("dict(slots=4, max_len=128, decode_block=4, requests=8, "
         "min_prompt=4, max_prompt=40, new_tokens=6, page_size=16)")
TRAIN = ("dict(batch=2, seq_len=32, steps=4, drain_every=2, pods=2, "
         "inner_steps=2, rounds=2)")
PAGED_MHA = ("dict(arch='minicpm-2b', slots=4, pages=12, page_size=16, "
             "pool_pages=48)")
BUILDS = ("import chip_smoke as cs\n"
          "from repro.launch import serve\n"
          "builds = serve.build_models([cs.ARCH], full=False)\n")


def _python(*argv, **env):
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, text=True, capture_output=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "PYTHONPATH": str(REPO / "src"), **env})


def _phase_lines(out):
    assert out.returncode == 0, out.stderr[-4000:]
    return [ln for ln in out.stdout.splitlines() if ln.startswith("[")]


def test_exits_nonzero_without_tpu():
    out = _python("chip_smoke.py")
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_one_chip_phases_rehearsed():
    out = _python("-c", BUILDS + (
        f"cs.run_serving({SERVE}, builds)\n"
        f"cs.run_training({TRAIN}, full=False)\n"
        f"cs.run_kernels(builds, {SERVE}, {TRAIN}, {PAGED_MHA}, "
        "interpret=True)\n"),
        REPRO_DECODE_ATTN="interpret")
    lines = _phase_lines(out)
    assert "[serve] paged greedy tokens bitwise equal to dense" in lines
    assert sum(ln.startswith("[train]") for ln in lines) == 2
    assert sum(ln.startswith("[kernel]") for ln in lines) == 7


def test_four_chip_phases_rehearsed():
    out = _python("-c", BUILDS + (
        "cs.run_diloco_mesh(dict(batch=4, seq_len=32, inner_steps=2), "
        "full=False)\n"
        "cs.run_plane(builds=builds)\n"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    lines = _phase_lines(out)
    assert sum(ln.startswith("[diloco4]") for ln in lines) == 3
    assert any(ln.startswith("[plane4] 4 replicas") for ln in lines)
