"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide §2,
rehearsals 1 and 2): the real script must FAIL at its device check when
there is no TPU, and everything around that check — kernels against the
gather path, the in-process HTTP serve phase with its SIGTERM shutdown,
the float32 agreement, the cache check, and the four-replica / sharded
phase on virtual devices — runs here at tiny size with the check stubbed
(``require_platform=None``) and the kernels in interpret mode."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _lines(out: str):
    rows = []
    for ln in out.splitlines():
        try:
            rows.append(json.loads(ln))
        except ValueError:
            pass
    return rows


def test_fails_at_device_phase_without_tpu(tmp_path):
    """JAX_PLATFORMS=cpu from outside: non-zero exit, the device line
    says cpu, and no contract line is printed."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=REPO)
    assert proc.returncode != 0
    rows = _lines(proc.stdout)
    assert rows[0]["phase"] == "device" and rows[0]["ok"] is False
    assert rows[0]["platform"] == "cpu"
    assert rows[-1]["phase"] == "failed"
    assert not any(r.get("ok") is True and "device" in r
                   and "phase" not in r for r in rows)
    assert len(rows) == 2, "no phase may run past a failed device check"


def test_fails_alone_in_a_directory(tmp_path):
    """The script without the program: non-zero exit, no result line."""
    alone = tmp_path / "alone"
    alone.mkdir()
    (alone / "chip_smoke.py").write_text(
        open(os.path.join(REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=str(alone))
    assert proc.returncode != 0
    assert not any(r.get("ok") is True for r in _lines(proc.stdout))


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DYN_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("DYN_JIT_FENCE", "raise")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    import jax

    knobs = ("jax_compilation_cache_dir",
             "jax_hlo_source_file_canonicalization_regex",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in knobs}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    yield chip_smoke.Settings(model="tiny", require_platform=None,
                              interpret=True, prompt_lens=(24, 100), osl=8,
                              trim_grid_4chip=False, tp=2)
    from jax.experimental.compilation_cache import compilation_cache as cc

    for k, v in was.items():  # enable_compile_cache() re-points them
        jax.config.update(k, v)
    cc.reset_cache()


def test_one_chip_phases_on_cpu(tiny, capsys):
    rc = chip_smoke.run_smoke(tiny)
    rows = _lines(capsys.readouterr().out)
    by = {r["phase"]: r for r in rows if "phase" in r}
    assert rc == 0, rows
    assert [r["phase"] for r in rows[:-1]] == [
        "device", "kernels", "serve", "agree", "cache"]
    assert all(r["ok"] for r in rows)
    serve = by["serve"]
    assert serve["requests_answered"] == 10
    assert serve["post_warmup_compiles"] == 0
    assert serve["server_exit"] == "clean" and serve["port_closed"]
    assert serve["compiles"] > 0 and serve["warmup_s"] > 0
    assert len(serve["ttft_s"]) == chip_smoke.N_STREAM
    assert by["agree"]["top1_equal"] == by["agree"]["positions"] == 9
    assert by["cache"]["entries"] > 0
    # the contract's last line, and nothing else on it
    assert set(rows[-1]) == {"ok", "device"}
    assert set(rows[-1]["device"]) == {"platform", "kind", "count"}


def test_four_chip_phases_on_virtual_devices(tiny, capsys):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs the forced multi-device CPU host")
    tiny.chips = 4
    rc = chip_smoke.run_smoke(tiny)
    rows = _lines(capsys.readouterr().out)
    by = {r["phase"]: r for r in rows if "phase" in r}
    assert rc == 0, rows
    assert [r["phase"] for r in rows[:-1]] == ["device", "replicas",
                                               "model4"]
    rep = by["replicas"]
    assert rep["identical_to_one_replica"] == chip_smoke.N_ROUTED
    assert len(set(rep["device_ids"].values())) == 4
    assert not any(rep["post_warmup_compiles"].values())
    assert by["model4"]["window_has_collective"]
    assert by["model4"]["greedy_prefix_equal"] == by["model4"]["of"]


def test_a_failed_phase_prints_no_result(tiny, capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "KERNEL_ATOL", -1.0)
    rc = chip_smoke.run_smoke(tiny)
    rows = _lines(capsys.readouterr().out)
    assert rc != 0
    assert rows[-1]["phase"] == "failed"
    assert [r["phase"] for r in rows] == ["device", "kernels", "failed"]
