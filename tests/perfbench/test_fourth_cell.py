"""A cell of a new configuration enters by files and entries alone (PR 38).

In a temporary root: the benchmark's files as committed, ``BENCHMARK.json``
as committed, and what a later ``model_config`` PR would bring for a family
whose configuration keys are *not* afmoe's (the SmallThinker row's names at
toy sizes: ``sliding_window_layout``, ``sliding_window_size``,
``moe_ffn_hidden_size``, ``moe_num_primary_experts``, no dense-layer key):
a configuration file with its ``flops`` group (``attention_keys``) and its
``kernels`` group, a mix, a reference module, a parameters file, and spec
files and entries under new names with a ``workloads`` list. No file that was
there is touched, the floor's check and every check ``test_data_driven.py``
runs over the real manifest pass over this one, and the kernels' and the
step's counts are what an independent count from the source's keys gives.
Before PR 38 this could not be done: the floor's test looked the count up in
a table of its own, and ``kernel_roofline`` read afmoe's key names."""

from __future__ import annotations

import json
import shutil
import types

import pytest

import manifest_checks
from perfbench import cluster, flops, kernel_counts, manifest
from perfbench.readers import kernel_roofline, read_spec
from perfbench_helpers import DATA, REPO
from test_data_driven import _digests

CONFIG, CELL = "smallthinker-toy", "smallthinker-toy.steps"
NEW_METRICS = {  # new name -> (the spec it is a copy of, what changes in the copy)
    "st_flash_window_ms": ("flash_window_ms", {}),
    "st_flash_window_roofline": ("flash_window_roofline", {"time_ms": {"metric": "st_flash_window_ms"}}),
    "st_moe_experts_ms": ("moe_experts_ms", {}),
    "st_moe_experts_roofline": ("moe_experts_roofline", {"time_ms": {"metric": "st_moe_experts_ms"}}),
}
SOURCE_KEYS = {  # the SmallThinker row's names, at sizes a test can count by hand
    "model_name": "smallthinker_toy",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "sliding_window_size": 256, "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 2, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "max_position_embeddings": 1024, "vocab_size": 256,
    "tie_word_embeddings": False,
}
HELD = 2  # of the 4 experts, as one of two chips that share a layer's experts
ROUTING = ("2026-10-02 00:00:00,000 hypha.executor.training INFO round 1 routing: steps=4 "
           "expert_layers=4 experts_held=2 pairs_routed=9000 pairs_computed=9000 "
           "pairs_per_token=1.0986 load_max=1300 load_mean=1125.0 load_max_over_mean=1.156 "
           "tokens_elsewhere=3000\n")


def configuration() -> dict:
    s = SOURCE_KEYS
    pairs_here = s["moe_num_active_primary_experts"] * HELD / s["moe_num_primary_experts"]
    return {
        "source": "test only: a family with SmallThinker's key names at toy sizes",
        **s,
        "share": {"experts_routed": s["moe_num_primary_experts"], "experts_held": HELD},
        "reduced": [], "assumed": {"router": "reads the layer's input before attention"},
        "job_sets": [],
        "flops": {
            "layers": 4, "width": 64, "heads": 4, "kv_heads": 2, "head_size": 16,
            # one matrix that stands for the router (64 x 4) and one pair's three (3 x 64 x 32)
            "mlp_width": int(3 * pairs_here * s["moe_ffn_hidden_size"]) + s["moe_num_primary_experts"],
            "mlp_matrices": 1, "vocabulary": 256,
            "attention_keys": [None, 256, 256, 256],
        },
        "kernels": {
            "flash_attention_window": {"layers": 3, "heads": 4, "kv_heads": 2, "head_size": 16, "window": 256},
            "grouped_swiglu": {"width": 64, "expert_width": 32, "held": HELD, "layers": 4},
        },
        "kernels_why": "three window layers of four; every layer has experts, none is dense",
        "checks": {
            "reference": "smallthinker_toy", "reference_tolerance": 0.02,
            "reference_reason": "a later PR brings its reference as a module of this name under "
                                "perfbench/reference/ and records the tolerance from its own seeds on the chip",
        },
    }


@pytest.fixture(scope="module")
def fourth(tmp_path_factory):
    """The root, its manifest, and the digests of the benchmark's files before
    the new ones were added."""
    root = tmp_path_factory.mktemp("fourth") / "checkout"
    shutil.copytree(REPO / "perfbench", root / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(REPO / manifest_checks.PARAMETERS, root / manifest_checks.PARAMETERS)
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(root)
    bench = root / "perfbench"
    # --- what the later PR adds: files ...
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(configuration(), indent=1))
    shutil.copy(DATA / "tiny.h4.json", bench / "traffic" / f"{CELL}.json")
    (bench / "reference" / "smallthinker_toy.py").write_text('"""The family\'s plain reference."""\n')
    (root / manifest_checks.PARAMETERS / f"{CONFIG}.json").write_text(json.dumps({
        "parameters": parameters_from_the_sources_keys(),
        "origin": "counted from the source's keys in test_fourth_cell.py; a real cell reads the AOT compile's state",
    }))
    entries = []
    for name, (copied, changed) in NEW_METRICS.items():
        spec = json.loads((bench / "layer_metrics" / f"{copied}.json").read_text())
        (bench / "layer_metrics" / f"{name}.json").write_text(json.dumps({**spec, **changed}))
        old = next(e for e in manifest.load_manifest(root)["per_layer"] if e["name"] == copied)
        entries.append({**old, "name": name, "workloads": [CELL]})
    # --- ... and entries
    m = manifest.load_manifest(root)
    m["configs"].append({"name": CONFIG, "source": configuration()["source"], "reduced": [],
                         "file": f"perfbench/configs/{CONFIG}.json", "why": "a later PR's"})
    m["workloads"].append({"name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1,
                           "why": "a later PR's"})
    m["per_layer"] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(m, indent=1))
    return root, m, before


def parameters_from_the_sources_keys() -> int:
    s = SOURCE_KEYS
    d, q, kv = s["hidden_size"], s["num_attention_heads"] * s["head_dim"], s["num_key_value_heads"] * s["head_dim"]
    layer = 2 * d * q + 2 * d * kv + d * s["moe_num_primary_experts"] + HELD * 3 * d * s["moe_ffn_hidden_size"] + 2 * d
    return s["num_hidden_layers"] * layer + 2 * s["vocab_size"] * d + d


def test_nothing_that_was_there_is_touched_and_the_manifest_gains_entries_only(fourth):
    root, m, before = fourth
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    added = sorted(set(after) - set(before))
    assert added == sorted(
        [f"perfbench/configs/{CONFIG}.json", f"perfbench/traffic/{CELL}.json",
         "perfbench/reference/smallthinker_toy.py"]
        + [f"perfbench/layer_metrics/{n}.json" for n in NEW_METRICS])
    real = manifest.load_manifest(REPO)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert m[key] == real[key]
    for key in ("configs", "workloads", "per_layer"):
        assert m[key][:len(real[key])] == real[key]  # what was there, as it was, first
    assert len(m["workloads"]) == 4 and len(m["configs"]) == 3
    assert len(m["per_layer"]) == len(real["per_layer"]) + len(NEW_METRICS)


def test_the_manifest_resolves_the_cell_with_the_metrics_every_cell_reports_and_its_own(fourth):
    root, m, _ = fourth
    cell = manifest.resolve(CELL, root)
    assert cell.config["moe_ffn_hidden_size"] == 32 and cell.traffic["sequence"] == 1024
    assert not {"layer_types", "sliding_window", "moe_intermediate_size", "num_experts",
                "num_dense_layers"} & set(cell.config)  # none of afmoe's names
    everyones = [e["name"] for e in m["per_layer"] if "workloads" not in e]
    assert [e["name"] for e, _ in cell.per_layer] == everyones + list(NEW_METRICS)
    assert [e["name"] for e in cell.end_to_end] == ["tokens_per_s", "sync_exposed_s", "setup_s"]
    # and the cells that were there report what they reported: 33, 33 and 40
    for name in manifest_checks.cell_names(manifest.load_manifest(REPO)):
        assert ([e["name"] for e, _ in manifest.resolve(name, root).per_layer]
                == [e["name"] for e, _ in manifest.resolve(name, REPO).per_layer])


def test_the_floors_check_passes_for_the_fourth_cell_and_for_those_that_were_there(fourth):
    root, m, _ = fourth
    held = {name: manifest_checks.check_work_dir_floor(m, name, root) for name in manifest_checks.cell_names(m)}
    assert held[CELL] == 16 * parameters_from_the_sources_keys() + 4 * 256 * 1024
    assert all(held[name] == manifest_checks.check_work_dir_floor(manifest.load_manifest(REPO), name, REPO)
               for name in held if name != CELL)


def test_the_issues_cell_would_pass_the_floor_and_would_not_have_passed_twice_its_size():
    """ISSUE 38's reckoning for the drawn row that fits: 559 267 840
    parameters and 25 MB of data are 8.97 GB there at once."""
    attention = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    layer = attention + 2560 * 64 + 16 * 3 * 2560 * 768
    parameters = 4 * layer + 2 * 18_992 * 2560
    assert (attention, layer, parameters) == (20_971_520, 115_507_200, 559_267_840)
    held = 16 * parameters + 4 * 768 * 8192
    assert round(held / 1e9, 2) == 8.97 and round(22 * parameters / 1e9, 2) == 12.30
    assert manifest_checks.WORK_MARGIN * held <= cluster.WORK_FREE_BYTES < 2 * held


def test_every_check_run_over_the_real_manifest_passes_over_this_one(fourth):
    root, m, _ = fourth
    manifest_checks.check_all(m, root, in_git=False)


def cell_and_run(root):
    cell = manifest.resolve(CELL, root)
    run = types.SimpleNamespace(texts={"w0": ROUTING}, measured=[{"round": 1}],
                                device={"kind": "TPU v5 lite", "count": 1})
    return cell, run


def test_the_window_kernels_counts_are_an_independent_count_from_the_sources_keys(fourth):
    cell, run = cell_and_run(fourth[0])
    s, t = SOURCE_KEYS, cell.traffic
    batch, seq, window, size = t["batch"], t["sequence"], s["sliding_window_size"], s["head_dim"]
    window_layers = sum(s["sliding_window_layout"])
    pairs = sum(min(i + 1, window) for i in range(seq))  # keys a query sees, over a sequence
    products = 2 + 5  # forward QK^T, PV; backward the scores again, dP, dV, dQ, dK
    q = batch * seq * s["num_attention_heads"] * size * 2
    kv = batch * seq * s["num_key_value_heads"] * size * 2
    mine = {
        "flops": float(window_layers * batch * s["num_attention_heads"] * pairs * products * 2 * size),
        # forward reads q, k, v and writes o; backward reads q, k, v, o, dO and writes dQ, dK, dV
        "bytes": float(window_layers * ((2 * q + 2 * kv) + (4 * q + 4 * kv))),
    }
    assert kernel_roofline.counts("flash_attention_window", run, cell) == mine


def test_the_grouped_products_counts_are_an_independent_count_from_the_sources_keys(fourth):
    cell, run = cell_and_run(fourth[0])
    s = SOURCE_KEYS
    d, f, layers = s["hidden_size"], s["moe_ffn_hidden_size"], s["num_hidden_layers"]
    pairs = 9000 / 4  # a step, from the routing line
    tables = layers * 3 * (HELD * d * f * 2)  # gate, up, down, bf16
    mine = {
        "flops": pairs * 9 * (2 * d * f),  # three products forward, six backward
        "bytes": float(3 * tables + 4 * pairs * d * 2),  # read twice, gradient written; rows in, out, both gradients
    }
    assert kernel_roofline.counts("grouped_swiglu", run, cell) == mine


def test_the_copied_specs_read_a_share_of_the_roofline_through_the_reader(fourth):
    cell, run = cell_and_run(fourth[0])
    specs = {e["name"]: spec for e, spec in cell.per_layer}
    values = {"st_flash_window_ms": 2.0, "st_moe_experts_ms": 0.5}
    for name, kernel, ms in (("st_flash_window_roofline", "flash_attention_window", 2.0),
                             ("st_moe_experts_roofline", "grouped_swiglu", 0.5)):
        need = kernel_roofline.counts(kernel, run, cell)
        least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
        assert read_spec(specs[name], run, cell, values) == pytest.approx(100 * least / (ms / 1000))
    # Trinity's spec reads Trinity's time, which this cell does not report
    assert read_spec(manifest.resolve("trinity-mini-d5.steps", REPO).per_layer[-1][1], run, cell, values) is None


def test_flops_per_token_with_attention_keys_is_the_count_by_hand(fourth):
    cell, _ = cell_and_run(fourth[0])
    s, seq = SOURCE_KEYS, cell.traffic["sequence"]
    d, q, kv = s["hidden_size"], s["num_attention_heads"] * s["head_dim"], s["num_key_value_heads"] * s["head_dim"]
    pairs_here = s["moe_num_active_primary_experts"] * HELD / s["moe_num_primary_experts"]
    layer = 2 * d * q + 2 * d * kv + d * s["moe_num_primary_experts"] + pairs_here * 3 * d * s["moe_ffn_hidden_size"]
    active = s["num_hidden_layers"] * layer + s["vocab_size"] * d
    assert active == flops.matmul_params(cell.config["flops"]) == 4 * (8192 + 4096 + 256 + 6144) + 16384
    seen = sum(min(seq, s["sliding_window_size"]) if windowed else seq for windowed in s["sliding_window_layout"])
    assert seen == 1024 + 3 * 256
    assert flops.flops_per_token(cell.config["flops"], seq) == 6 * active + 12 * q * seen == 1_923_072
    # an entry is cut to the sequence, so the group holds at a shorter one too
    assert flops.flops_per_token(cell.config["flops"], 128) == 6 * active + 12 * q * 4 * 128
    # without the key every layer is full, as before PR 38 and to the bit
    full = {k: v for k, v in cell.config["flops"].items() if k != "attention_keys"}
    assert flops.flops_per_token(full, seq) == 6.0 * active + 12.0 * 4 * q * seq
    assert flops.flops_per_token(dict(full, attention_keys=[None] * 4), seq) == flops.flops_per_token(full, seq)
    assert kernel_counts.band_pairs(seq, 256) < kernel_counts.band_pairs(seq, None)
