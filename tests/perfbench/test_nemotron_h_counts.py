"""The numbers ``nemotron-twotower-ctx-d7`` brings: its ``flops`` group and its
parameter count against independent counts from the source's keys, the scan's
and the two-matrix experts' operations and bytes against counts by hand, its
``kernels`` group, and the readers of the cell's own ten per-layer metrics on
one recorded step of the cell (``data/recorded_nemotron_h/``)."""

from __future__ import annotations

import json
import types

import pytest

from perfbench import flops, kernel_counts, manifest, relu2_counts, ssd_counts
from perfbench.readers import kernel_roofline, nemotron_h_roofline, read_spec

from perfbench_helpers import DATA as FIXTURES, REPO

CONFIG = json.loads((REPO / "perfbench" / "configs" / "nemotron-twotower-ctx-d7.json").read_text())
TRAFFIC = manifest.load_traffic(REPO / "perfbench" / "traffic" / "nemotron-twotower-ctx-d7.steps.json")
CELL_NAME = "nemotron-twotower-ctx-d7.steps"
# The metrics only this cell reports, with their specs, in the manifest's order.
OWN = {e["name"]: (e, s) for e, s in manifest.resolve(CELL_NAME, REPO).per_layer
       if e.get("workloads") == [CELL_NAME]}
SPECS = {name: spec for name, (_, spec) in OWN.items()}
RECORDED = FIXTURES / "recorded_nemotron_h"
KINDS = ["mamba2", "experts", "mamba2", "experts", "mamba2", "full_attention", "experts"]


def sizes():
    c = CONFIG
    d, hd = c["hidden_size"], c["head_dim"]
    di = c["mamba_num_heads"] * c["mamba_head_dim"]
    return (d, c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, di,
            di + 2 * c["n_groups"] * c["ssm_state_size"], c["mamba_num_heads"],
            c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"])


def test_the_flops_group_gives_the_count_from_the_sources_keys():
    c, s = CONFIG, TRAFFIC["sequence"]
    d, q, kv, di, wide, heads, f, fs = sizes()
    mamba = d * (di + wide + heads) + di * d
    attention = 2 * d * q + 2 * d * kv
    held_share = c["num_experts_per_tok"] * c["n_routed_experts"] / c["share"]["experts_routed"]
    experts = d * c["share"]["experts_routed"] + 2 * d * fs + held_share * 2 * d * f
    assert (mamba, attention, held_share, experts) == (38_707_200, 23_396_352, 0.375, 24_041_472)
    active = 3 * mamba + attention + 3 * experts + c["vocab_size"] * d
    assert active == 255_682_560 == flops.matmul_params(c["flops"])
    assert c["flops"]["mlp_width"] * d == 3 * mamba + 3 * experts == 188_246_016
    assert c["flops"]["attention_keys"] == [None if k == "full_attention" else 0 for k in KINDS]
    mine = 6 * active + 12 * q * s  # one layer over the whole sequence, no causal discount
    assert mine == flops.flops_per_token(c["flops"], s)
    if s == 8192:
        assert mine == 1_936_748_544
    # the shares of a step's matmul FLOPs by kind of part (PERF.md 4): Mamba-2 45 %, experts 28 %
    assert round(100 * 3 * mamba / active) == 45 and round(100 * 3 * experts / active) == 28
    # what is left out: the scan's products, 1.6 % of the count
    scan = ssd_counts.ssd_scan(1, s, 64, 64, 128, 8, 128, 3)["flops"] / s
    assert "LEFT OUT" in c["flops_why"] and 0.015 < scan / mine < 0.017
    # the formula with this model's seven blocks as attention-and-MLP layers would read too high
    naive = dict(c["flops"], layers=7, mlp_width=f, mlp_matrices=2)
    del naive["attention_keys"]
    assert flops.flops_per_token(naive, s) / mine > 1.5


def test_the_parameters_are_the_issues_arithmetic_and_the_compiles_state():
    c = CONFIG
    d, q, kv, di, wide, heads, f, fs = sizes()
    mamba = d + d * (di + wide + heads) + c["conv_kernel"] * wide + wide + 3 * heads + di + di * d
    attention = d + 2 * d * q + 2 * d * kv
    experts = d + d * c["share"]["experts_routed"] + c["n_routed_experts"] * 2 * d * f + 2 * d * fs
    assert (mamba, attention, experts) == (38_744_896, 23_399_040, 100_125_312)
    total = 3 * mamba + attention + 3 * experts + 2 * c["vocab_size"] * d + d  # untied: twice
    recorded = json.loads((FIXTURES / "parameters" / "nemotron-twotower-ctx-d7.json").read_text())
    assert total == 528_092_736 == recorded["parameters"]  # the AOT compile's state (PERF.md 4)
    assert 11.61e9 < 22 * total < 11.63e9 and 2.11e9 < 4 * total < 2.12e9


def test_the_state_the_step_compiles_over_holds_that_many_parameters():
    """The program's own count at the cell's sizes, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import build_model

    conf = {}
    for s in CONFIG["job_sets"][1:]:
        key, value = s.removeprefix("job.model_config.").split("=", 1)
        conf[key] = json.loads(value)
    model, cfg = build_model({"family": "nemotron_h", "config": conf})
    assert list(cfg.layer_types) == KINDS
    variables = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 64), jnp.int32))
    leaves = jax.tree.leaves(variables["params"])
    assert (sum(x.size for x in leaves), len(leaves)) == (528_092_736, 53)
    assert sum(x.size for x in jax.tree.leaves(variables["moe_state"])) == 3 * 128  # beside them


def test_the_scans_count_by_hand():
    # one layer, one chunk of 4 positions, 2 heads of 3 in 1 group, a state of 5.
    # Forward: C B^T 2 x 4 x 4 x 5 = 160 a group; a head: the mixing 2 x 4 x 4 x 3 = 96, the chunk's
    # state and the carried state's part 2 x 4 x 3 x 5 = 120 each. Backward: C B^T again and two
    # gradient products each: 4 x 160 + 3 x 2 x (96 + 240); a boundary 6 x 2 x 3 x 5
    c = ssd_counts.ssd_scan(1, 4, 2, 3, 5, 1, 4, 1, element_bytes=2)
    assert c["flops"] == 4 * 160 + 3 * 2 * (96 + 240) + 6 * 2 * 3 * 5
    # bytes a position: x 6, B, C 5 each = 16 elements of 2; the step 2 x 4; y 6 x 4: 64 forward;
    # backward the 16 again and their gradients (64), the step and its gradient (16), dy (24): 104;
    # a boundary state 2 x 3 x 5 x 4 written and read
    assert c["bytes"] == 4 * (64 + 104) + 2 * 120
    cell = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    step = nemotron_h_roofline.counts("ssd_scan", None, cell)
    assert set(kernel_roofline.KERNELS) == {"flash_attention_window", "grouped_swiglu"}  # left alone
    tokens = TRAFFIC["batch"] * TRAFFIC["sequence"]
    # 10.5 MFLOP a token and layer, as ISSUE 46 reckoned ("about 10")
    assert 10.4e6 < step["flops"] / (3 * tokens) < 10.6e6
    # the memory's bound is the larger: 1.03 ms a layer and sequence of 8192 against 0.44 ms of products
    per_layer = {k: v / 3 / tokens * 8192 for k, v in step.items()}
    assert 1e3 * per_layer["bytes"] / 819e9 == pytest.approx(1.03, abs=0.01)
    assert 1e3 * per_layer["flops"] / 197e12 == pytest.approx(0.44, abs=0.01)
    # the states of every position, which no scan here may write: 17 GB a sequence and layer
    assert 8192 * 64 * 64 * 128 * 4 == 17_179_869_184 > 20 * per_layer["bytes"]
    # the boundary states the backward pass keeps: 64 of 2 MB
    assert (8192 // 128, 64 * 64 * 128 * 4) == (64, 2_097_152)


def test_the_two_matrix_experts_count_by_hand():
    # 5 pairs through experts of 4 -> 3 -> 4: two products forward (2 x 4 x 3 each a pair), their
    # data and weight gradients backward: 12 x 4 x 3 a pair; two tables of 2 x 4 x 3 elements
    c = relu2_counts.grouped_relu2(5, 4, 3, 2, 1, element_bytes=2)
    assert c == {"flops": 12.0 * 4 * 3 * 5, "bytes": float(3 * 2 * 2 * 4 * 3 * 2 + 4 * 5 * 4 * 2)}
    three = kernel_counts.grouped_swiglu(5, 4, 3, 2, 1, element_bytes=2)
    assert c["flops"] == three["flops"] * 2 / 3  # the gated expert's count, left as it is, is a half more


def test_the_kernels_group_is_the_count_from_the_sources_keys():
    c, k = CONFIG, CONFIG["kernels"]
    assert k["flash_attention_full"] == {
        "layers": KINDS.count("full_attention"), "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_size": c["head_dim"], "window": None}
    assert k["ssd_scan"] == {
        "layers": KINDS.count("mamba2"), "heads": c["mamba_num_heads"], "head_size": c["mamba_head_dim"],
        "state": c["ssm_state_size"], "groups": c["n_groups"], "chunk": c["chunk_size"]}
    assert k["grouped_relu2"] == {
        "layers": KINDS.count("experts"), "width": c["hidden_size"],
        "expert_width": c["moe_intermediate_size"], "held": c["n_routed_experts"]}
    assert len(c["kernels_why"]) > 100 and len(c["flops_why"]) > 100
    # the call over the whole triangle at 32 heads of 128: 1.92 TFLOP a sequence of 8192, 9.8 ms at the peak
    full = kernel_counts.flash_attention(1, 8192, 32, 2, 128, None)
    assert full["flops"] == 14 * 128 * 32 * (8192 * 8193 // 2) and 1.92e12 < full["flops"] < 1.93e12


@pytest.fixture(scope="module")
def recorded():
    """One traced step of the cell (the mix says 1 step here), and the
    routing line of the round it was cut from."""
    cell = types.SimpleNamespace(config=CONFIG, traffic=dict(TRAFFIC, inner_steps=1))
    line = (RECORDED / "routing_line.txt").read_text()
    run = types.SimpleNamespace(out_dir=RECORDED, texts={"w0": line}, measured=[{"round": 1}],
                                device={"kind": "TPU v5 lite", "count": 1})
    return cell, run


def test_the_cells_own_ten_are_listed_and_their_specs_name_readers_that_exist():
    assert list(OWN) == [
        "nemo_mamba2_ms", "nemo_ssd_ms", "nemo_ssd_roofline", "nemo_flash_full_ms", "nemo_flash_full_roofline",
        "nemo_moe_route_ms", "nemo_moe_experts_ms", "nemo_moe_experts_roofline", "nemo_moe_pairs_per_token",
        "nemo_moe_load_max_over_mean"]
    for name, (entry, spec) in OWN.items():
        assert (spec["layer"], spec["unit"], spec["moves"]) == (entry["layer"], entry["unit"], "tokens_per_s")
        assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()
        assert name.endswith("_roofline") == (entry["unit"] == "%")
    # LFM2's specs for the routed layer, copied under this cell's names: the same readers over the same
    # scopes and fields, but for the share, which counts two products and not three
    for mine in ("moe_route_ms", "moe_experts_ms", "moe_pairs_per_token", "moe_load_max_over_mean"):
        theirs = json.loads((REPO / "perfbench" / "layer_metrics" / f"lfm2_{mine}.json").read_text())
        assert SPECS[f"nemo_{mine}"] == theirs
    assert SPECS["nemo_moe_experts_roofline"]["kernel"] == "grouped_relu2"
    # the 33 that every cell reports, and the ten; no other cell reports these
    cell, other = manifest.resolve(CELL_NAME, REPO), manifest.resolve("lfm2-24b-a2b-d5.steps", REPO)
    assert len(cell.per_layer) == 33 + len(OWN) == 43
    assert not set(OWN) & {e["name"] for e, _ in other.per_layer}
    assert [e["name"] for e in cell.end_to_end] == ["tokens_per_s", "sync_exposed_s", "setup_s"]


def test_the_ten_metrics_read_the_recorded_step(recorded):
    cell, run = recorded
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, run, cell, values)
    assert all(v is not None for v in values.values()), values
    expect = json.loads((RECORDED / "readings.json").read_text())
    assert {k: repr(v) for k, v in values.items()} == expect  # digit for digit what the readers gave
    # the scan is inside the Mamba-2 mixer, whose projections, convolution and gated norm are the rest of it
    assert 0 < values["nemo_ssd_ms"] < values["nemo_mamba2_ms"]
    for share in ("nemo_ssd_roofline", "nemo_flash_full_roofline", "nemo_moe_experts_roofline"):
        assert 0 < values[share] < 100, (share, values[share])
    # the share gives 6 choices x 8 held / 128 = 0.375 at seeded routers; by round 1 the routers have
    # learnt to send half of that to the held experts (PERF.md 6, PR 46), and one expert holds most of it
    assert 0.1 < values["nemo_moe_pairs_per_token"] < 0.375 and values["nemo_moe_load_max_over_mean"] > 2
    assert nemotron_h_roofline.counts("flash_attention_full", run, cell) == {
        name: TRAFFIC["batch"] * v
        for name, v in kernel_counts.flash_attention(1, TRAFFIC["sequence"], 32, 2, 128, None).items()}
    pairs = values["nemo_moe_pairs_per_token"] * 3 * TRAFFIC["batch"] * TRAFFIC["sequence"]
    assert nemotron_h_roofline.counts("grouped_relu2", run, cell)["flops"] == pytest.approx(
        12.0 * 2688 * 1856 * pairs, rel=1e-3)


def test_the_scans_scope_covers_its_backward_pass_in_the_recorded_step(recorded):
    from perfbench.readers import device_scope

    _, run = recorded
    events = device_scope.device_events(device_scope.load(run.out_dir))
    scan = [e for e in events if device_scope.matches(e, ["ssd_scan"], [])]
    assert any("transpose(" in e["args"]["tf_op"] for e in scan) and any(
        "transpose(" not in e["args"]["tf_op"] for e in scan)
    kernels = sorted({e["name"].split(".")[0] for e in events if e["name"].startswith("flash_attention")})
    assert kernels == ["flash_attention", "flash_attention_bwd"]  # one call over the whole triangle, no window
    count = sum(device_scope.matches(e, SPECS["nemo_flash_full_ms"]["scopes"], SPECS["nemo_flash_full_ms"]["names"])
                for e in events)
    assert count == 3  # forward one kernel, backward two


def test_with_no_trace_and_on_a_program_without_the_scopes_the_readers_return_nothing(recorded, tmp_path):
    """What the parent commit gives under this PR's benchmark files: no
    scope, no routing line, no kernel of this family, and no reader raises."""
    cell, run = recorded
    gone = types.SimpleNamespace(**{**vars(run), "out_dir": tmp_path, "texts": {"w0": ""}})
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, gone, cell, values)
    assert set(values.values()) == {None}


@pytest.mark.parametrize("kernel,time_ms", [("ssd_scan", "nemo_ssd_ms"), ("flash_attention_full", "nemo_flash_full_ms"),
                                           ("grouped_relu2", "nemo_moe_experts_ms")])
@pytest.mark.parametrize("missing", ["the_group", "the_entry", "a_key_of_the_entry"])
def test_a_configuration_without_the_entry_reads_nothing_and_says_why(capsys, missing, kernel, time_ms, recorded):
    _, run = recorded
    config = {k: v for k, v in CONFIG.items() if k != "kernels"}
    if missing == "the_entry":
        config["kernels"] = {"flash_attention_window": {"layers": 1}}
    elif missing == "a_key_of_the_entry":
        config["kernels"] = {kernel: {k: v for k, v in CONFIG["kernels"][kernel].items() if k != "layers"}}
    bare = types.SimpleNamespace(config=config, traffic=TRAFFIC)
    spec = next(s for s in SPECS.values() if s.get("kernel") == kernel)
    assert read_spec(spec, run, bare, {time_ms: 50.0}) is None
    said = capsys.readouterr().err
    assert said.startswith("perfbench: ") and ("layers" if missing == "a_key_of_the_entry" else kernel) in said
    whole = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    assert read_spec(spec, run, whole, {time_ms: 50.0}) > 0
    assert read_spec(spec, run, whole, {}) is None  # no time: a program without the scope
    nowhere = types.SimpleNamespace(**{**vars(run), "device": {"kind": "cpu", "count": 1}})
    assert read_spec(spec, nowhere, whole, {time_ms: 50.0}) is None  # no peak: never an assumed one


# What ``BENCHMARK.json`` held at the parent commit (f98571c), by digest of each
# list as ``json.dumps(..., sort_keys=True)`` gives it: this PR adds entries
# after them and changes none.
AT_THE_PARENT = {
    "configs": (4, "6f3770fd07c96b332e6b5a0bf89b269fc2a60dfd8257d0b1df7bb74016959e20"),
    "workloads": (5, "2f885ef50b804b1c6285483e1871248bf4d7b7adb77240209e767cf1d9f3df57"),
    "per_layer": (71, "111fbb0566c2dfd8e2738e4cebbd45f158d0e5e8f47365ed766b483674f07a40"),
}
THE_REST_AT_THE_PARENT = "995383cb1e2cba2874cd07d3cfdf89f7d4e022cc78631faa1a7da53661652dbe"


def _digest(obj) -> str:
    import hashlib

    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()


def test_the_manifest_gained_entries_only_and_what_was_there_comes_first_as_it_was():
    m = manifest.load_manifest(REPO)
    for key, (count, digest) in AT_THE_PARENT.items():
        assert _digest(m[key][:count]) == digest, key
    assert _digest({k: m[k] for k in ("command", "paths", "run_seconds", "end_to_end")}) == THE_REST_AT_THE_PARENT
    # what this PR added follows; a later cell's entries come after these and
    # are none of this test's business (no count of a whole list is held)
    assert m["configs"][4]["name"] == "nemotron-twotower-ctx-d7"
    assert m["workloads"][5]["name"] == CELL_NAME and m["workloads"][5]["chips"] == 1
    assert [e["name"] for e in m["per_layer"][71:81]] == list(OWN)
    assert all(len(w["why"]) <= 200 for w in m["workloads"]) and all(len(c["why"]) <= 200 for c in m["configs"])
