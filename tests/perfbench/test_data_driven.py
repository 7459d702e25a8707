"""A later PR adds a cell, a configuration, a mix or a metric by adding files
and manifest entries, and edits no file that is there. And the manifest as
committed keeps to the contract's shapes (the checks themselves are
``manifest_checks.py``'s, so that ``test_fourth_cell.py`` can run them over a
manifest a later PR would bring)."""

from __future__ import annotations

import hashlib
import json
from pathlib import Path

import pytest

import manifest_checks
from perfbench_helpers import (
    REPO, all_rounds_sound, failing_checks, rehearsal_result, run_bench,
)

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())


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


@pytest.mark.parametrize("metric", manifest_checks.metrics(MANIFEST), ids=lambda m: m["name"])
def test_metric_entry_keeps_to_the_contract(metric):
    manifest_checks.check_metric_entry(MANIFEST, metric)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_an_end_to_end_metric_of_every_cell_it_is_in(metric):
    manifest_checks.check_per_layer_entry(MANIFEST, metric, REPO)


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_names_files_that_exist_and_git_would_commit(cell):
    manifest_checks.check_cell_entry(MANIFEST, cell, REPO)


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file_agree(config):
    manifest_checks.check_configuration(config, REPO)


def test_manifest_shape():
    manifest_checks.check_shape(MANIFEST, REPO)
