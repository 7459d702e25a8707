"""The numbers ``trinity-mini-d5`` brings: its ``flops`` group against an
independent count from the source's keys, the kernels' operations and bytes
against hand counts, and the readers of the cell's own seven per-layer
device and routing metrics (listed since PR 37, each with a ``workloads`` list)
on one recorded step of the cell (``data/recorded_afmoe/``). A later configuration that
brings metrics of its own brings a test like this one beside its record."""

from __future__ import annotations

import json
import types

import pytest

from perfbench import flops, kernel_counts, manifest
from perfbench.readers import device_scope, kernel_roofline, read_spec

from perfbench_helpers import DATA as FIXTURES, REPO

CONFIG = json.loads((REPO / "perfbench" / "configs" / "trinity-mini-d5.json").read_text())
TRAFFIC = manifest.load_traffic(REPO / "perfbench" / "traffic" / "trinity-mini-d5.steps.json")
CELL_NAME = "trinity-mini-d5.steps"
# The metrics only this cell reports, with their specs, in the manifest's order.
OWN = {e["name"]: (e, s) for e, s in manifest.resolve(CELL_NAME, REPO).per_layer
       if e.get("workloads") == [CELL_NAME]}
SPECS = {name: spec for name, (_, spec) in OWN.items()}
RECORDED = FIXTURES / "recorded_afmoe"


def test_the_flops_group_gives_the_count_from_the_sources_keys():
    c, s = CONFIG, TRAFFIC["sequence"]
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    attention = d * q * 3 + d * kv * 2  # q, gate, o; k, v
    dense = 3 * d * c["intermediate_size"]
    expert = 3 * d * c["moe_intermediate_size"]
    routed = c["share"]["experts_routed"]
    pairs_here = c["num_experts_per_tok"] * c["num_experts"] / routed  # half a pair a token
    expert_layer = expert * c["num_shared_experts"] + d * routed + pairs_here * expert
    layers, leading = c["num_hidden_layers"], c["num_dense_layers"]
    active = (layers * attention + leading * dense + (layers - leading) * expert_layer
              + c["vocab_size"] * d)
    assert active == 264_110_080 == flops.matmul_params(c["flops"])
    kinds = [c["layer_types"][i] for i in c["layers_run"]]
    seen = sum(min(s, c["sliding_window"]) if k == "sliding_attention" else s for k in kinds)
    mine = 6 * active + 12 * q * seen
    assert mine == flops.flops_per_token(c["flops"], s) == 2_389_966_848
    # the formula with this model's layers as they are would read too high
    naive = dict(c["flops"], layers=5, mlp_width=c["intermediate_size"], mlp_matrices=3)
    assert 1.5 < flops.flops_per_token(naive, s) / mine < 1.7


def test_the_parameters_are_the_issues_arithmetic():
    c = CONFIG
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    attention = d * 4096 * 3 + d * 512 * 2 + 2 * 128  # and the two head norms
    norms = 4 * d
    dense = attention + norms + 3 * d * c["intermediate_size"]
    expert_layer = attention + norms + 3 * d * f + d * 128 + c["num_experts"] * 3 * d * f
    total = dense + 4 * expert_layer + 2 * c["vocab_size"] * d + d
    assert total == 504_147_200  # what the AOT compile's state holds (PERF.md 4)


def test_a_band_is_counted_by_the_pairs_it_holds():
    assert kernel_counts.band_pairs(4, None) == 10  # 1 + 2 + 3 + 4
    assert kernel_counts.band_pairs(4, 2) == 1 + 2 + 2 + 2
    assert kernel_counts.band_pairs(4, 9) == 10
    assert kernel_counts.band_pairs(8192, 2048) == sum(min(i + 1, 2048) for i in range(8192))
    full = kernel_counts.flash_attention(1, 8192, 32, 4, 128, None)
    cut = kernel_counts.flash_attention(1, 8192, 32, 4, 128, 2048)
    assert cut["flops"] / full["flops"] == pytest.approx(0.4375, abs=0.0002)  # ISSUE 29's 0.44
    assert full["bytes"] == cut["bytes"] == (6 * 32 + 6 * 4) * 8192 * 128 * 2


def test_flash_attention_by_hand():
    # one head of size 2, three positions, no window: 6 pairs; forward 4 x 2 a
    # pair (two products), backward 10 x 2 (five)
    c = kernel_counts.flash_attention(1, 3, 1, 1, 2, None, element_bytes=2)
    assert c["flops"] == 6 * (8 + 20)
    assert c["bytes"] == (3 * 2 * 2) * (4 + 8)  # q, k, v, o; then q, k, v, o, dO, dQ, dK, dV


def test_the_grouped_product_by_hand():
    # 5 pairs, width 4, expert width 3, 2 experts held, 1 layer: three products
    # of 2 x 4 x 3 a pair forward, six backward
    c = kernel_counts.grouped_swiglu(5, 4, 3, 2, 1, element_bytes=2)
    assert c["flops"] == 5 * 9 * (2 * 4 * 3)
    assert c["bytes"] == 3 * (3 * 2 * 4 * 3 * 2) + 4 * (5 * 4 * 2)
    assert kernel_counts.roofline_share({"flops": 197e12, "bytes": 0.0}, 2.0, 197e12, 819e9) == 50.0
    assert kernel_counts.roofline_share({"flops": 0.0, "bytes": 819e9}, 4.0, 197e12, 819e9) == 25.0
    with pytest.raises(KeyError):
        kernel_counts.peak_bytes_per_s("cpu")


@pytest.fixture(scope="module")
def recorded():
    """One traced step of the cell (H 8 then, so the mix says 1 step here)."""
    cell = types.SimpleNamespace(config=CONFIG, traffic=dict(TRAFFIC, inner_steps=1))
    line = ("2026-09-28 00:57:39,731 hypha.executor.training INFO round 1 routing: steps=8 "
            "expert_layers=4 experts_held=8 pairs_routed=213030 pairs_computed=213030 "
            "pairs_per_token=0.8126 load_max=3593 load_mean=832.15 load_max_over_mean=4.318 "
            "tokens_elsewhere=110487\n")
    run = types.SimpleNamespace(out_dir=RECORDED, texts={"w0": line}, measured=[{"round": 1}],
                                device={"kind": "TPU v5 lite", "count": 1})
    return cell, run


def test_the_cells_own_seven_are_listed_and_their_specs_name_readers_that_exist():
    assert list(OWN) == [
        "moe_pairs_per_token", "moe_load_max_over_mean", "moe_route_ms", "moe_experts_ms",
        "moe_experts_roofline", "flash_window_ms", "flash_window_roofline"]
    assert not (FIXTURES / "afmoe_layer_metrics.json").exists()  # they wait no longer
    for name, (entry, spec) in OWN.items():
        assert (spec["layer"], spec["unit"], spec["moves"]) == (entry["layer"], entry["unit"], "tokens_per_s")
        assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()
        assert name.endswith("_roofline") == (entry["unit"] == "%")
    # Mistral's cells do not report them: 33 there, 40 here.
    other = manifest.resolve("mistral-7b-d1.steps", REPO)
    assert not set(OWN) & {e["name"] for e, _ in other.per_layer}
    cell = manifest.resolve(CELL_NAME, REPO)
    assert len(other.per_layer) + len(OWN) == len(cell.per_layer)
    # and every cell reports the same three end-to-end metrics
    assert [e["name"] for e in cell.end_to_end] == [e["name"] for e in other.end_to_end] == [
        "tokens_per_s", "sync_exposed_s", "setup_s"]


def test_the_seven_metrics_read_the_recorded_step(recorded):
    cell, run = recorded
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, run, cell, values)
    assert values["moe_pairs_per_token"] == 0.8126 and values["moe_load_max_over_mean"] == 4.318
    # milliseconds a step, from the device's events of that step
    assert 40 < values["flash_window_ms"] < 60 and 15 < values["moe_experts_ms"] < 30
    assert 20 < values["moe_route_ms"] < 40
    for share in ("moe_experts_roofline", "flash_window_roofline"):
        assert 5 < values[share] < 100, (share, values[share])
    # Digit for digit what the reader gave from the source's keys before PR 38
    # took the shapes from the ``kernels`` group (read at ccfad04 from this record).
    assert {k: repr(v) for k, v in values.items()} == {
        "moe_pairs_per_token": "0.8126", "moe_load_max_over_mean": "4.318",
        "moe_route_ms": "29.316408", "moe_experts_ms": "20.216935",
        "moe_experts_roofline": "25.2389715146574",
        "flash_window_ms": "52.711625", "flash_window_roofline": "32.429002171105765"}
    assert kernel_roofline.counts("flash_attention_window", run, cell) == {
        "flops": 3367489241088.0, "bytes": 1811939328.0}
    assert kernel_roofline.counts("grouped_swiglu", run, cell) == {
        "flops": 1005201653760.0, "bytes": 1644244992.0}


def test_the_kernels_group_is_the_count_from_the_sources_keys():
    """The group states numbers; that they are this configuration's is held
    here, from the source's keys and the layers that are run."""
    c, k = CONFIG, CONFIG["kernels"]
    kinds = [c["layer_types"][i] for i in c["layers_run"]]
    assert kinds == ["sliding_attention"] * 4 + ["full_attention"]  # as job_sets gives them to the program
    assert k["flash_attention_window"] == {
        "layers": kinds.count("sliding_attention"), "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_size": c["head_dim"], "window": c["sliding_window"]}
    assert k["grouped_swiglu"] == {
        "width": c["hidden_size"], "expert_width": c["moe_intermediate_size"], "held": c["num_experts"],
        "layers": c["num_hidden_layers"] - c["num_dense_layers"]}
    assert set(k) == {s["kernel"] for s in SPECS.values() if s["reader"] == "kernel_roofline"}
    assert len(c["kernels_why"]) > 100


@pytest.mark.parametrize("missing", ["the_group", "the_entry", "a_key_of_the_entry"])
def test_a_configuration_without_the_kernels_entry_reads_nothing_and_says_why(recorded, capsys, missing):
    cell, run = recorded
    config = {k: v for k, v in CONFIG.items() if k != "kernels"}
    if missing == "the_entry":
        config["kernels"] = {"grouped_swiglu": CONFIG["kernels"]["grouped_swiglu"]}
    elif missing == "a_key_of_the_entry":
        config["kernels"] = {"flash_attention_window": {"layers": 4, "heads": 32}}
    bare = types.SimpleNamespace(config=config, traffic=cell.traffic)
    values = {"flash_window_ms": 52.711625, "moe_experts_ms": 20.216935}
    assert read_spec(SPECS["flash_window_roofline"], run, bare, values) is None
    said = capsys.readouterr().err
    assert said.startswith("perfbench: ") and (
        "kv_heads" if missing == "a_key_of_the_entry" else "no 'flash_attention_window'") in said
    # the other kernel's share is read where its entry is there
    other = read_spec(SPECS["moe_experts_roofline"], run, bare, values)
    assert (other == 25.2389715146574) if missing == "the_entry" else (other is None)
    with pytest.raises(ValueError, match="unknown kernel"):
        kernel_roofline.counts("no_such_kernel", run, cell)


def test_a_while_and_the_operations_inside_it_count_once(recorded):
    events = device_scope.device_events(device_scope.load(RECORDED))
    inside = device_scope.busy_seconds(events, ["moe_combine"], [])
    both = device_scope.busy_seconds(events, ["moe_combine"], ["while"])
    loops = device_scope.busy_seconds(events, [], ["while"])
    assert 0 < inside < loops and both == pytest.approx(loops)
    assert device_scope.busy_seconds(events, ["no_such_scope"], []) == 0.0
    # a scope is a whole component of the path: "router" is not "moe_router"
    assert device_scope.matches({"name": "fusion.1", "args": {"tf_op": "jit(step)/x/router/dot:"}}, ["router"], [])
    assert not device_scope.matches({"name": "fusion.1", "args": {"tf_op": "jit(step)/moe_router/dot:"}}, ["router"], [])


def test_with_no_trace_and_on_a_program_without_the_scopes_the_readers_return_nothing(recorded, tmp_path):
    cell, run = recorded
    gone = types.SimpleNamespace(**{**vars(run), "out_dir": tmp_path, "texts": {"w0": ""}})
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, gone, cell, values)
    assert set(values.values()) == {None}
