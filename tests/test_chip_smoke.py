"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases hold at a tiny size with the Pallas kernels interpreted.

The script itself only ever runs on the chip; these tests keep its glue
(tuning, the resident serve, the decode-step kernel count, the logits check
and the mesh comparisons) working between chip runs.  In interpret mode a
kernel lowers to plain HLO, so the decode-step check counts the kernel's
name instead of a Mosaic custom call.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

from conftest import subproc_env

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro import configs  # noqa: E402
from repro.kernels import ops  # noqa: E402

TINY = dict(TUNE_STEPS=3, TUNE_BATCH=2, TUNE_SEQ=32, KERNEL_MARK="quant_gemv")


def _run(args, cwd, env):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=600, cwd=cwd, env=env)


def test_refuses_without_a_tpu():
    res = _run([str(ROOT / "chip_smoke.py")], ROOT, subproc_env())
    assert res.returncode != 0
    assert "needs a TPU; JAX found platform 'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


def test_refuses_outside_the_repo(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = subproc_env()
    del env["PYTHONPATH"]
    res = _run(["chip_smoke.py"], tmp_path, env)
    assert res.returncode != 0
    assert "No module named 'repro'" in res.stderr
    assert '"ok"' not in res.stdout


def test_one_chip_phases_at_tiny_size(monkeypatch, capsys):
    for name, value in TINY.items():
        monkeypatch.setattr(chip_smoke, name, value)
    with ops.force_impl("interpret"):
        chip_smoke.one_chip(configs.make_tiny(chip_smoke.smoke_config()))
    out = capsys.readouterr().out
    n = len(chip_smoke.REQUESTS)
    assert f"served {n}/{n} requests" in out
    assert "2 prefill shapes" in out          # one GEMM and one GEMV bucket
    assert "logits vs float32 reference" in out


_FOUR = textwrap.dedent(f"""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {str(ROOT)!r})
    import chip_smoke
    from repro import configs
    from repro.kernels import ops
    for name, value in {TINY!r}.items():
        setattr(chip_smoke, name, value)
    with ops.force_impl("interpret"):
        chip_smoke.four_chips(configs.make_tiny(chip_smoke.smoke_config()))
    print(chip_smoke.device_line())
""")


def test_four_chip_phases_at_tiny_size():
    """Sharded Engine vs one device, and the (2, 2) mesh train step vs one
    device, on four virtual CPU devices."""
    res = _run(["-c", _FOUR], ROOT, subproc_env())
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    assert "tokens equal True" in res.stdout
    assert lines[-1].endswith('"count": 4}}'), lines[-1]
