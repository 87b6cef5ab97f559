"""BENCHMARK.json against the files it names, and the command's refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import harness

SPEC = harness.Spec()
DATA = SPEC.data
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_names():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in DATA[g]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in DATA["end_to_end"] + DATA["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert 1 <= DATA["run_seconds"] <= 51
    assert os.path.join(*DATA["command"][1:]).startswith("bench/")


def test_every_name_finds_its_files():
    for c in DATA["configs"]:
        cfg = SPEC.config(c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in DATA["workloads"]:
        _, cfg, _, Loop = harness.cell_parts(SPEC, w["name"])
        assert callable(Loop.build) and Loop.unit
        harness.cell_parts(SPEC, w["name"], control=True)
        assert cfg["control"]
    for m in DATA["end_to_end"] + DATA["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_every_cell_reports_setup_another_and_a_layer():
    e2e = {m["name"] for m in DATA["end_to_end"]}
    for w in DATA["workloads"]:
        mine = {m["name"] for m in SPEC.metrics(w["name"], False)}
        assert "setup_s" in mine and len(mine) >= 2
        layers = SPEC.metrics(w["name"], True)
        assert layers
        for m in layers:
            assert m["moves"] in mine and m["moves"] in e2e
    for m in DATA["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if not k.startswith("REPRO")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         DATA["workloads"][0]["name"], "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_command_refuses_the_cpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0 and not _printed_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and not _printed_result(p.stdout)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 99, 2 ** 40 + 3])
def test_seed_words_cover_large_seeds(seed):
    from problem import seed_words
    lo, hi = seed_words(seed)
    assert 0 <= lo < 2 ** 32 and 0 <= hi < 2 ** 32
    assert lo + (hi << 32) == seed
