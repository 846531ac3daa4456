"""The harness refuses to measure anywhere but a known TPU, finds every
part of a cell by name, and takes a new cell as files and entries only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

ROOT = harness.ROOT


def _run(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT, "--workload", "ea3d_1m.anneal", "--seed",
             str(2 ** 31 + 12345), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_unknown_device_kind_is_an_error(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v99 imaginary"

    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.NoChip, match="TPU v99 imaginary"):
        harness.device_info(1)


def test_too_few_chips_is_an_error(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    devs, peaks = harness.device_info(1)
    assert peaks["hbm_bytes_per_s"] == 819e9 and len(devs) == 1
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.device_info(4)


def test_benchmark_alone_is_not_enough(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to measure: no result, non-zero exit."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "ea3d_1m.anneal", "--seed", "7",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_part_of_every_cell_is_found_by_name():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cell in spec["workloads"]:
        _, _, cfg, traffic = harness.load_cell(cell["name"])
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "drivers", traffic["driver"] + ".py"))
        assert cfg["name"] == cell["config"]
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        mod = harness.load_module(
            os.path.join(ROOT, "bench", "metrics", m["name"] + ".py"), "m")
        assert mod.read({}) is None       # nothing to read: no number


def test_new_cell_is_files_and_entries_only(tmp_path):
    """A later cell adds a traffic file and a workload entry; no file of
    the harness changes."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = json.load(open(tmp_path / "bench" / "traffic" /
                             "anneal.json"))
    traffic["schedule"] = dict(traffic["schedule"], sweeps=4096)
    traffic["record_points"] = [256, 1024, 4096]
    json.dump(traffic, open(tmp_path / "bench" / "traffic" /
                            "anneal_long.json", "w"))
    spec = json.load(open(tmp_path / "BENCHMARK.json"))
    spec["workloads"].append({"name": "ea3d_1m.anneal_long",
                              "config": "ea3d_1m", "traffic": "anneal_long",
                              "chips": 1, "why": "4096-sweep anneals"})
    json.dump(spec, open(tmp_path / "BENCHMARK.json", "w"))
    _, cell, cfg, t = harness.load_cell("ea3d_1m.anneal_long",
                                        root=str(tmp_path))
    assert cell["traffic"] == "anneal_long" and cfg["L"] == 100
    assert t["schedule"]["sweeps"] == 4096
    with pytest.raises(KeyError):
        harness.load_cell("ea3d_1m.anneal_long")     # not in the real file
