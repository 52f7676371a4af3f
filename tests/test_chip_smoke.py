"""chip_smoke.py cannot rot: its CPU rehearsal runs inside tier-1, and the
compile-cache placement rule it reports is pinned in fresh interpreters."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rehearsal_drives_every_stage(capsys):
    """Build -> index -> query -> serve in-process at a tiny scale factor:
    every stage and every check of the chip run, on the CPU backend."""
    import chip_smoke

    result = chip_smoke.main(["--rehearse-on-cpu", "--sf", "0.005"])
    assert result["ok"] is False and "rehearsal" in result  # never a pass
    assert result["native"] == "live"
    assert result["mosaic"] == {"build": False, "minmax": False}  # interpreted on cpu
    device_pass = "\n".join(result["dispatch_device"])
    for tag in ("filter: device", "join: device-smj", "agg: device-grouped-scan"):
        assert tag in device_pass
    # the last stdout line is the verdict the driver reads: exactly these keys,
    # with the device as JAX reports it; the line before is the full report
    report_line, verdict_line = capsys.readouterr().out.strip().splitlines()[-2:]
    verdict = json.loads(verdict_line)
    assert verdict == {"ok": False, "device": result["device"]}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["count"], int)
    assert report_line.startswith("report: ")
    assert json.loads(report_line[len("report: "):]) == result


def test_fails_without_a_chip():
    import chip_smoke

    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.main([])


def _cache_dir_seen_by_a_fresh_process(env_value):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, hyperspace_tpu; print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120, check=True,
    )
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_obeys_the_environment(tmp_path):
    assert _cache_dir_seen_by_a_fresh_process(str(tmp_path)) == str(tmp_path)


def test_compile_cache_defaults_to_the_fixed_in_checkout_path():
    assert _cache_dir_seen_by_a_fresh_process(None) == os.path.join(REPO, ".jax_cache")
