"""BENCHMARK.json against the rules the harness and the driver hold it to,
and the command's refusal of anything but the chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import generator, harness

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_text_use_the_allowed_characters():
    b = _bench()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names)), group
        for e in b[group]:
            assert NAME.match(e["name"]), e["name"]
            text = {"configs": ("why", "source"), "workloads": ("why",),
                    "per_layer": ("layer",)}.get(group, ())
            for k in text:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k], e[k]
    for word in b["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in b["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    assert len(json.dumps(b).encode()) <= 64 * 1024


def test_every_cell_resolves_to_files_and_reports_enough():
    b = _bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        spec = harness.cell_spec(b, w["name"])
        cfg = harness.load_json(ROOT, spec["config_entry"]["file"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "families", cfg["family"] + ".py"))
        generator.validate(harness.load_json(ROOT, "bench", "traffic", w["traffic"] + ".json"))
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert spec["per_layer"], w["name"]
        for m in spec["end_to_end"] + spec["per_layer"]:
            assert os.path.isfile(harness.reader_path(m["name"]))
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            spec = harness.cell_spec(b, cell)
            assert m["moves"] in {x["name"] for x in spec["end_to_end"]}, (m["name"], cell)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(b["workloads"]) // 2)


def test_a_dotted_metric_falls_back_to_its_quantity_reader():
    def own(quantity):
        return os.path.join(harness.BENCH, "metrics", quantity + ".py")

    assert harness.reader_path("idle_share.product.4chip") == own("idle_share")
    assert harness.reader_path("product_s") == own("product_s")
    with pytest.raises(FileNotFoundError):
        harness.reader_path("no_such_quantity.product")


def test_bounds_and_run_length_fit_the_check():
    b = _bench()
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells: 2 + 14 * 24 runs, each allowed run_seconds
    # + 60 s, 2 x 90 s of compiling per cell and 1200 s spare, in 43,200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43_200


def test_unknown_device_kind_and_cpu_are_refused():
    import jax

    with pytest.raises(harness.NoDevice):
        harness.peaks_for("TPU v99 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.NoDevice):
        harness.devices_for(jax, 1, require_tpu=True)


def _run_command(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "spgemm.banded-100k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_the_cpu():
    proc = _run_command(ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_the_command_refuses_a_directory_without_the_library(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
