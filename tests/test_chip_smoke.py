"""chip_smoke.py off the chip: its phases rehearse at tiny size on the CPU
backend, and nothing but a full-size run on a TPU is ever "ok".

The rehearsals run the real entry in a child (its own JAX, its own device
count); no test here touches an accelerator.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, *, devices=1, cwd=REPO, script=SMOKE, timeout=900):
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu", "TPP_COMPILE_CACHE": "0",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
    }
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, script, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=cwd,
    )
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines else None
    return proc, lines, last


def test_real_entry_without_a_tpu_exits_nonzero_and_says_not_ok(tmp_path):
    proc, lines, last = _run(["--out", str(tmp_path / "out")])
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert last["ok"] is False and last["error"] == "no TPU"
    assert last["device"]["platform"] == "cpu"
    assert not any(l.startswith("phase ") for l in lines)  # nothing ran
    assert not (tmp_path / "out").exists()


def test_alone_in_a_directory_it_fails(tmp_path):
    """``chip_smoke.py`` and nothing else of the repo: no result, not ok."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc, _, last = _run(
        [], cwd=str(tmp_path), script=str(tmp_path / "chip_smoke.py")
    )
    assert proc.returncode != 0
    assert last["ok"] is False and last["device"] is None
    assert "tpu_pipelines" in last["error"]


def test_one_chip_phases_rehearse_at_tiny_size_on_the_cpu(tmp_path):
    proc, lines, last = _run(
        ["--size", "tiny", "--out", str(tmp_path / "out")]
    )
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    for phase in ("native", "pipeline", "serve", "kernels", "generate"):
        assert any(l.startswith(f"phase {phase}: ok") for l in lines), phase
    text = "\n".join(lines)
    for node in ("CsvExampleGen", "StatisticsGen", "SchemaGen", "Transform",
                 "Trainer", "Evaluator", "Pusher"):
        assert f"node {node}: COMPLETE" in text
    assert "Transform materialised on the device: True" in text
    assert "jit fallbacks after warm-up: 0" in text
    assert "decode compiles after warm-up: 0" in text
    # A rehearsal is never a chip run.
    assert last == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        "rehearsal": "all phases passed",
    }


def test_four_chip_phase_rehearses_on_forced_host_devices(tmp_path):
    proc, lines, last = _run(
        ["--size", "tiny", "--chips", "4", "--out", str(tmp_path / "out")],
        devices=4,
    )
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    # Only the multi-chip phase runs under --chips 4.
    assert [l for l in lines if l.startswith("phase ") and ": ok" in l] == [
        l for l in lines if l.startswith("phase mesh: ok")
    ]
    text = "\n".join(lines)
    assert "fsdp vs one device: max |loss diff|" in text
    assert "(0.250)" in text                      # fsdp bytes per device
    assert "bitwise equal params: True" in text   # ordered mode
    assert "four replicas on devices [0, 1, 2, 3]" in text
    assert last["ok"] is False and last["device"]["count"] == 4


def test_wrong_device_count_is_refused(tmp_path):
    proc, _, last = _run(
        ["--size", "tiny", "--out", str(tmp_path / "out")], devices=4
    )
    assert proc.returncode == 2
    assert last["ok"] is False and "--chips 1" in last["error"]


def test_injected_phase_failure_exits_nonzero(tmp_path, monkeypatch, capsys):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    monkeypatch.setattr(chip_smoke, "_device_report", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1,
    })

    def boom():
        raise RuntimeError("injected")

    monkeypatch.setattr(chip_smoke, "_native_cores", boom)
    rc = chip_smoke.main(["--size", "tiny", "--out", str(tmp_path / "out")])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 1
    last = json.loads(out[-1])
    assert last["ok"] is False and last["failed_phase"] == "native"
    assert "injected" in last["error"]
    assert not any(l.startswith("phase pipeline") for l in out)
