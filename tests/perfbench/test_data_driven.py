"""A later PR adds a cell, a configuration, a mix or a metric by adding files
and manifest entries, and edits no file that is there. And the manifest as
committed keeps to the contract's shapes."""

from __future__ import annotations

import hashlib
import json
import re
import subprocess
from pathlib import Path

import pytest

from perfbench_helpers import (
    REPO, all_rounds_sound, failing_checks, rehearsal_result, run_bench,
)

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _digests(root: Path) -> dict[str, str]:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted((root / "perfbench").rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_a_new_cell_configuration_mix_and_metric_are_files_and_entries_only(bench_root):
    before = _digests(bench_root)
    bench = bench_root / "perfbench"
    cfg = json.loads((bench / "configs" / "tiny-gpt2.json").read_text())
    cfg["job_sets"] = [s.replace("n_embd=32", "n_embd=64") for s in cfg["job_sets"]]
    cfg["flops"]["width"], cfg["flops"]["head_size"] = 64, 32
    (bench / "configs" / "wider-gpt2.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "tiny.h4.json").read_text())
    mix["inner_steps"] = 2
    # What the job does differently is the mix's own --set strings: the
    # int8 cell PERF.md 7 plans is a file like this one, and no code.
    mix["job_sets"] = ["job.delta_codec=int8"]
    (bench / "traffic" / "tiny.h2.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "loss_last.json").write_text(json.dumps({
        "layer": "Inner step", "unit": "nat", "moves": "tokens_per_s",
        "reader": "log_field", "role": "w0", "line": "round_done",
        "field": "loss_last", "rounds": "measured", "reduce": "last",
    }))
    manifest = json.loads((bench_root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "wider-gpt2", "source": "test only", "reduced": [],
        "file": "perfbench/configs/wider-gpt2.json", "why": "a later PR's",
    })
    manifest["workloads"].append({
        "name": "wider-gpt2.h2", "config": "wider-gpt2", "traffic": "tiny.h2",
        "chips": 1, "why": "a later PR's",
    })
    manifest["per_layer"].append({
        "name": "loss_last", "unit": "nat", "better": "lower",
        "source": "program_counter", "layer": "Inner step",
        "moves": "tokens_per_s", "workloads": ["wider-gpt2.h2"],
    })
    (bench_root / "BENCHMARK.json").write_text(json.dumps(manifest))

    # A window long enough for a round on a machine that runs five other
    # tests beside this one (5 s once closed none).
    r = run_bench(bench_root, "--workload", "wider-gpt2.h2", "--seed", "11",
                  "--seconds", "30", "--trace", "1")
    assert r.returncode == 3, r.stderr[-3000:]
    result = rehearsal_result(r.stderr)
    assert result["attempted"] >= 1 and all_rounds_sound(result, r.stdout)
    assert failing_checks(r.stdout) == {"attention_is_compiled_flash", "device_is_tpu"}
    spans = bench_root / "chiprun_out/perfbench/wider-gpt2.h2/traced/spans/spans-w0.jsonl"
    encodes = [json.loads(x) for x in spans.read_text().splitlines() if '"encode"' in x]
    assert encodes and {x["attrs"]["codec"] for x in encodes} == {"int8"}  # it reached the program
    assert 0 < result["metrics"]["loss_last"]["value"] < 6
    assert result["metrics"]["loss_last"]["unit"] == "nat"
    rounds = [json.loads(x) for x in r.stdout.splitlines() if '"phase": "round"' in x]
    assert {x["steps"] for x in rounds} == {2}  # the new mix's H, not the old one's
    # The old cell does not report the new cell's metric.
    from perfbench import manifest as m

    old = m.resolve("tiny-gpt2.h4", bench_root)
    assert "loss_last" not in {e["name"] for e, _ in old.per_layer}
    after = _digests(bench_root)
    assert {k: after[k] for k in before} == before  # nothing that was there changed


def _metrics():
    return MANIFEST["end_to_end"] + MANIFEST["per_layer"]


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_metric_entry_keeps_to_the_contract(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
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


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_an_end_to_end_metric_of_every_cell_it_is_in(metric):
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert metric["moves"] in e2e
    cells = metric.get("workloads") or [w["name"] for w in MANIFEST["workloads"]]
    moved = e2e[metric["moves"]]
    assert set(cells) <= set(moved.get("workloads") or [w["name"] for w in MANIFEST["workloads"]])
    spec = json.loads((REPO / "perfbench" / "layer_metrics" / f"{metric['name']}.json").read_text())
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        metric["layer"], metric["unit"], metric["moves"],
    )
    assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_names_files_that_exist_and_git_would_commit(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    config = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    files = [config["file"], f"perfbench/traffic/{cell['traffic']}.json"]
    for f in files:
        assert (REPO / f).is_file(), f
        assert any(f.startswith(p + "/") for p in MANIFEST["paths"])
    ignored = subprocess.run(
        ["git", "check-ignore", *files], cwd=str(REPO), capture_output=True, text=True,
    )
    assert ignored.returncode == 1 and ignored.stdout == "", ignored.stdout


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file_agree(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    body = json.loads((REPO / config["file"]).read_text())
    assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
    assert all(NAME.match(k) and k in body for k in config["reduced"])
    assert not any(re.search(r"(_dim|_rank|hidden_size|intermediate_size)$", k)
                   for k in config["reduced"])
    assert isinstance(body["assumed"], dict)
    checks = body["checks"]
    if "reference" in checks:  # a module of the benchmark's own, and a tolerance with its reason
        assert (REPO / "perfbench" / "reference" / f"{checks['reference']}.py").is_file()
        assert 0 < checks["reference_tolerance"] < 0.03 and len(checks["reference_reason"]) > 100
        assert "loss_first_tolerance" not in checks  # the reference replaces the band
    else:
        assert checks["loss_first_tolerance"] > 0


def test_manifest_shape():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = [m["name"] for m in _metrics()]
    assert len(names) == len(set(names))
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)
