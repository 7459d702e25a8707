"""What the tests hold a manifest and the files it names to, as functions of
the manifest and its root: ``test_data_driven.py`` and ``test_work_dir.py``
run them over ``BENCHMARK.json`` as committed, ``test_fourth_cell.py`` over
a copy to which a later PR's configuration and cell were added as files and
entries alone. A check that fails raises ``AssertionError``."""

from __future__ import annotations

import json
import re
import subprocess
from pathlib import Path

from perfbench import cluster, manifest as bench_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
PARAMETERS = Path("tests") / "perfbench" / "data" / "parameters"
# Specified and in no cell's reach: their spans exist only when the PS reads
# or writes its momentum file, which it does under a ``checkpoint_dir`` alone.
UNLISTED = {"ps_step_load_s", "ps_step_save_momentum_s"}
# What the work directory's floor promises a cell beyond what it holds there.
WORK_MARGIN = 1.25


def cell_names(manifest: dict) -> list[str]:
    return [w["name"] for w in manifest["workloads"]]


def metrics(manifest: dict) -> list[dict]:
    return manifest["end_to_end"] + manifest["per_layer"]


def parameters_of(manifest: dict, cell_name: str, root: Path) -> int:
    """The configuration's parameter count, from the file it brings."""
    config = next(w["config"] for w in manifest["workloads"] if w["name"] == cell_name)
    path = root / PARAMETERS / f"{config}.json"
    assert path.is_file(), (
        f"configuration {config!r} brings no parameter count: add {PARAMETERS / (config + '.json')} "
        'as {"parameters": <the train state\'s count>, "origin": <where it was read>}')
    body = json.loads(path.read_text())
    assert isinstance(body["parameters"], int) and body["parameters"] > 0 and len(body["origin"]) > 20
    return body["parameters"]


def check_work_dir_floor(manifest: dict, cell_name: str, root: Path) -> int:
    """What the cell holds in its work directory at once, in bytes: delta,
    the PS's copy, the update and the worker's copy (4 x 4 B a parameter) and
    the data. The one floor (``cluster.WORK_FREE_BYTES``) leaves it a quarter
    more than that, and no configuration has to state a size to the harness."""
    parameters = parameters_of(manifest, cell_name, root)
    cell = bench_manifest.resolve(cell_name, root, manifest)
    need = 16 * parameters + 4 * cell.traffic["data"]["sequences"] * cell.traffic["sequence"]
    assert WORK_MARGIN * need <= cluster.WORK_FREE_BYTES, (
        f"{cell_name} holds {need / 1e9:.2f} GB in its work directory at once; with a quarter "
        f"more that is over the {cluster.WORK_FREE_BYTES / 1e9:.0f} GB a run asks to be free")
    assert "parameters" not in cell.config
    return need


def check_metric_entry(manifest: dict, metric: dict) -> None:
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in manifest["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        allowed |= {"layer", "moves"}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in {"lower", "higher"}
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads") or []) <= set(cell_names(manifest))


def check_per_layer_entry(manifest: dict, metric: dict, root: Path) -> None:
    """It moves an end-to-end metric of every cell it is in, and its spec
    agrees with its entry and names a reader that exists."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert metric["moves"] in e2e
    cells = metric.get("workloads") or cell_names(manifest)
    moved = e2e[metric["moves"]]
    assert set(cells) <= set(moved.get("workloads") or cell_names(manifest))
    spec = json.loads((root / "perfbench" / "layer_metrics" / f"{metric['name']}.json").read_text())
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        metric["layer"], metric["unit"], metric["moves"],
    )
    assert (root / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()


def check_cell_entry(manifest: dict, cell: dict, root: Path, in_git: bool = True) -> None:
    """It names files that exist under ``paths`` and, in the repository, that
    git would commit."""
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    files = [config["file"], f"perfbench/traffic/{cell['traffic']}.json"]
    for f in files:
        assert (root / f).is_file(), f
        assert any(f.startswith(p + "/") for p in manifest["paths"])
    if in_git:
        ignored = subprocess.run(
            ["git", "check-ignore", *files], cwd=str(root), capture_output=True, text=True,
        )
        assert ignored.returncode == 1 and ignored.stdout == "", ignored.stdout


def check_configuration(config: dict, root: Path) -> None:
    """The manifest's entry and the configuration's file agree."""
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    body = json.loads((root / config["file"]).read_text())
    assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
    assert all(NAME.match(k) and k in body for k in config["reduced"])
    assert not any(re.search(r"(_dim|_rank|hidden_size|intermediate_size)$", k)
                   for k in config["reduced"])
    assert isinstance(body["assumed"], dict)
    checks = body["checks"]
    if "reference" in checks:  # a module of the benchmark's own, and a tolerance with its reason
        assert (root / "perfbench" / "reference" / f"{checks['reference']}.py").is_file()
        assert 0 < checks["reference_tolerance"] < 0.03 and len(checks["reference_reason"]) > 100
        assert "loss_first_tolerance" not in checks  # the reference replaces the band
    else:
        assert checks["loss_first_tolerance"] > 0


def check_shape(manifest: dict, root: Path) -> None:
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    assert 1 <= manifest["run_seconds"] <= 51
    for names in ([m["name"] for m in metrics(manifest)], cell_names(manifest),
                  [c["name"] for c in manifest["configs"]], [c["file"] for c in manifest["configs"]]):
        assert len(names) == len(set(names))
    assert {c["name"] for c in manifest["configs"]} == {w["config"] for w in manifest["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len((root / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def check_specs_are_listed(manifest: dict, root: Path) -> None:
    """Every spec file is listed but the two no cell can read, and every
    entry has its spec: no count, a later PR adds its own."""
    specs = {p.stem for p in (root / "perfbench" / "layer_metrics").glob("*.json")}
    listed = {e["name"] for e in manifest["per_layer"]}
    assert specs - listed == UNLISTED and listed <= specs


def check_all(manifest: dict, root: Path, in_git: bool = True) -> None:
    """Every check above, over every entry."""
    check_shape(manifest, root)
    for metric in metrics(manifest):
        check_metric_entry(manifest, metric)
    for metric in manifest["per_layer"]:
        check_per_layer_entry(manifest, metric, root)
    for config in manifest["configs"]:
        check_configuration(config, root)
    for cell in manifest["workloads"]:
        check_cell_entry(manifest, cell, root, in_git)
        check_work_dir_floor(manifest, cell["name"], root)
    check_specs_are_listed(manifest, root)
