"""The numbers ``lfm2-24b-a2b-d5`` brings: its ``flops`` group and its
parameter count against independent counts from the source's keys, the
convolution core's operations and bytes against a count by hand, its
``kernels`` group, and the readers of the cell's own ten per-layer metrics on
one recorded step of the cell (``data/recorded_lfm2_moe/``)."""

from __future__ import annotations

import json
import types

import pytest

from perfbench import flops, kernel_counts, manifest, short_conv_counts
from perfbench.readers import kernel_roofline, read_spec, short_conv_roofline

from perfbench_helpers import DATA as FIXTURES, REPO

CONFIG = json.loads((REPO / "perfbench" / "configs" / "lfm2-24b-a2b-d5.json").read_text())
TRAFFIC = manifest.load_traffic(REPO / "perfbench" / "traffic" / "lfm2-24b-a2b-d5.steps.json")
CELL_NAME = "lfm2-24b-a2b-d5.steps"
# The metrics only this cell reports, with their specs, in the manifest's order.
OWN = {e["name"]: (e, s) for e, s in manifest.resolve(CELL_NAME, REPO).per_layer
       if e.get("workloads") == [CELL_NAME]}
SPECS = {name: spec for name, (_, spec) in OWN.items()}
RECORDED = FIXTURES / "recorded_lfm2_moe"
KINDS = [CONFIG["layer_types"][i] for i in CONFIG["layers_run"]]


def test_the_layers_run_are_one_dense_conv_layer_and_one_whole_period():
    assert KINDS == ["conv", "conv", "conv", "conv", "full_attention"]
    assert CONFIG["num_hidden_layers"] == len(KINDS) and CONFIG["num_dense_layers"] == 1


def test_the_flops_group_gives_the_count_from_the_sources_keys():
    c, s = CONFIG, TRAFFIC["sequence"]
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    conv = d * 3 * d + d * d  # in and out projection
    attention = 2 * d * q + 2 * d * kv  # q, o; k, v
    dense = 3 * d * c["intermediate_size"]
    expert = 3 * d * c["moe_intermediate_size"]
    routed = c["share"]["experts_routed"]
    pairs_here = c["num_experts_per_tok"] * c["num_experts"] / routed  # half a pair a token
    expert_layer = d * routed + pairs_here * expert  # no shared expert
    layers, leading = c["num_hidden_layers"], c["num_dense_layers"]
    active = (KINDS.count("conv") * conv + KINDS.count("full_attention") * attention
              + leading * dense + (layers - leading) * expert_layer + c["vocab_size"] * d)
    assert active == 202_899_456 == flops.matmul_params(c["flops"])
    seen = sum(s if k == "full_attention" else 0 for k in KINDS)
    assert c["flops"]["attention_keys"] == [None if k == "full_attention" else 0 for k in KINDS]
    mine = 6 * active + 12 * q * seen
    assert mine == flops.flops_per_token(c["flops"], s) == 1_418_723_328
    # the shares of a step's matmul FLOPs that the cell's ``why`` states
    shares = {"conv": 4 * conv, "dense": dense, "head": c["vocab_size"] * d,
              "routed": 4 * expert_layer, "attention": attention}
    assert {k: round(100 * v / active) for k, v in shares.items()} == {
        "conv": 33, "dense": 36, "head": 17, "routed": 10, "attention": 5}
    # the formula with this model's layers as they are would read too high
    naive = dict(c["flops"], layers=5, mlp_width=c["intermediate_size"], mlp_matrices=3)
    del naive["attention_keys"]
    assert 2.5 < flops.flops_per_token(naive, s) / mine < 2.7


def test_the_parameters_are_the_issues_arithmetic_and_the_compiles_state():
    c = CONFIG
    d, f, hd = c["hidden_size"], c["moe_intermediate_size"], c["head_dim"]
    conv = d * 3 * d + c["conv_L_cache"] * d + d * d
    assert conv == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    attention = 2 * d * 32 * hd + 2 * d * 8 * hd + 2 * hd
    assert attention == 10_485_888
    dense, router, expert, norms = 3 * d * c["intermediate_size"], d * 64, 3 * d * f, 2 * d
    assert (dense, router, expert) == (72_351_744, 131_072, 9_437_184)
    first = conv + dense + norms
    conv_expert = conv + router + c["num_experts"] * expert + norms
    attention_expert = attention + router + c["num_experts"] * expert + norms
    assert (first, conv_expert, attention_expert) == (89_139_200, 92_416_000, 86_118_528)
    total = first + 3 * conv_expert + attention_expert + d + c["vocab_size"] * d  # tied: counted once
    recorded = json.loads((FIXTURES / "parameters" / "lfm2-24b-a2b-d5.json").read_text())
    assert total == 486_062_208 == recorded["parameters"]  # the AOT compile's state (PERF.md 4)
    assert 22 * total < 10.7e9 and 16 * total + 25e6 < 7.81e9


def test_the_state_the_step_compiles_over_holds_that_many_parameters():
    """The program's own count at the cell's sizes, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import build_model

    conf = {}
    for s in CONFIG["job_sets"][1:]:
        key, value = s.removeprefix("job.model_config.").split("=", 1)
        conf[key] = json.loads(value)
    model, _ = build_model({"family": "lfm2_moe", "config": conf})
    variables = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 64), jnp.int32))
    leaves = jax.tree.leaves(variables["params"])
    assert (sum(x.size for x in leaves), len(leaves)) == (486_062_208, 49)
    assert sum(x.size for x in jax.tree.leaves(variables["moe_state"])) == 4 * 64  # beside them


def test_the_convolutions_core_by_hand():
    # one layer, 3 positions, 2 channels, 3 taps: 6 elements; 11 values of 2 B an
    # element (B, C, x in, y out; dy, B, C, x in, dB, dC, dx out), 7 + 8 x 3 operations
    c = short_conv_counts.short_conv(1, 3, 2, 3, 1, element_bytes=2)
    assert c == {"flops": 6.0 * 31, "bytes": 6.0 * 11 * 2}
    cell = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    step = short_conv_roofline.counts(cell)
    assert set(kernel_roofline.KERNELS) == {"flash_attention_window", "grouped_swiglu"}  # left alone
    tokens = TRAFFIC["batch"] * TRAFFIC["sequence"]
    assert step["bytes"] == 4 * tokens * 2048 * 11 * 2  # ISSUE 40's 1.48 GB a sequence of 8192
    assert step["bytes"] / tokens * 8192 == 1_476_395_008
    # bound by bandwidth on the chip the cell runs on: 1.8 ms a sequence at 819 GB/s
    assert step["bytes"] / 819e9 > 50 * step["flops"] / 197e12
    assert 1e3 * 1_476_395_008 / 819e9 == pytest.approx(1.80, abs=0.01)


def test_the_kernels_group_is_the_count_from_the_sources_keys():
    c, k = CONFIG, CONFIG["kernels"]
    assert k["flash_attention_window"] == {
        "layers": KINDS.count("full_attention"), "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_size": c["head_dim"], "window": None}
    assert k["grouped_swiglu"] == {
        "width": c["hidden_size"], "expert_width": c["moe_intermediate_size"], "held": c["num_experts"],
        "layers": c["num_hidden_layers"] - c["num_dense_layers"]}
    assert k["short_conv"] == {"layers": KINDS.count("conv"), "width": c["hidden_size"],
                               "taps": c["conv_L_cache"]}
    assert len(c["kernels_why"]) > 100 and len(c["flops_why"]) > 100
    # the full layer's count: the whole causal triangle at head size 64, 0.96 TFLOP a sequence
    full = kernel_counts.flash_attention(1, 8192, 32, 8, 64, None)
    assert full["flops"] == 14 * 64 * 32 * (8192 * 8193 // 2) and 0.95e12 < full["flops"] < 0.97e12
    assert 1e3 * full["flops"] / 197e12 == pytest.approx(4.9, abs=0.05)


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
        "lfm2_conv_operator_ms", "lfm2_short_conv_ms", "lfm2_short_conv_roofline",
        "lfm2_flash_full_ms", "lfm2_flash_full_roofline", "lfm2_moe_route_ms", "lfm2_moe_experts_ms",
        "lfm2_moe_experts_roofline", "lfm2_moe_pairs_per_token", "lfm2_moe_load_max_over_mean"]
    for name, (entry, spec) in OWN.items():
        assert (spec["layer"], spec["unit"], spec["moves"]) == (entry["layer"], entry["unit"], "tokens_per_s")
        assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()
        assert name.endswith("_roofline") == (entry["unit"] == "%")
    # Trinity's five, copied under this cell's names: the same readers over the same scopes
    for mine in ("moe_route_ms", "moe_experts_ms", "moe_pairs_per_token", "moe_load_max_over_mean"):
        theirs = json.loads((REPO / "perfbench" / "layer_metrics" / f"{mine}.json").read_text())
        assert SPECS[f"lfm2_{mine}"] == theirs
    assert SPECS["lfm2_moe_experts_roofline"]["time_ms"] == {"metric": "lfm2_moe_experts_ms"}
    # 33 that every cell reports, and the ten; no other cell reports these
    cell, other = manifest.resolve(CELL_NAME, REPO), manifest.resolve("trinity-mini-d5.steps", REPO)
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
    # the core is inside the operator, and the operator's projections are most of it
    assert 0 < values["lfm2_short_conv_ms"] < values["lfm2_conv_operator_ms"]
    for share in ("lfm2_short_conv_roofline", "lfm2_flash_full_roofline", "lfm2_moe_experts_roofline"):
        assert 1 < values[share] < 100, (share, values[share])
    assert kernel_roofline.counts("flash_attention_window", run, cell) == {
        name: TRAFFIC["batch"] * v
        for name, v in kernel_counts.flash_attention(1, 8192, 32, 8, 64, None).items()}


def test_with_no_trace_and_on_a_program_without_the_scopes_the_readers_return_nothing(recorded, tmp_path):
    """What the parent commit gives under this PR's benchmark files: no
    scope, no routing line of this family, and no reader raises."""
    cell, run = recorded
    gone = types.SimpleNamespace(**{**vars(run), "out_dir": tmp_path, "texts": {"w0": ""}})
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, gone, cell, values)
    assert set(values.values()) == {None}


@pytest.mark.parametrize("missing", ["the_group", "the_entry", "a_key_of_the_entry"])
def test_a_configuration_without_the_short_conv_entry_reads_nothing_and_says_why(capsys, missing):
    config = {k: v for k, v in CONFIG.items() if k != "kernels"}
    if missing == "the_entry":
        config["kernels"] = {"grouped_swiglu": CONFIG["kernels"]["grouped_swiglu"]}
    elif missing == "a_key_of_the_entry":
        config["kernels"] = {"short_conv": {"layers": 4, "width": 2048}}
    bare = types.SimpleNamespace(config=config, traffic=TRAFFIC)
    run = types.SimpleNamespace(device={"kind": "TPU v5 lite", "count": 1})
    spec = SPECS["lfm2_short_conv_roofline"]
    assert read_spec(spec, run, bare, {"lfm2_short_conv_ms": 5.0}) is None
    said = capsys.readouterr().err
    assert said.startswith("perfbench: ") and ("taps" if missing == "a_key_of_the_entry" else "short_conv") in said
    whole = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    assert read_spec(spec, run, whole, {"lfm2_short_conv_ms": 5.0}) > 0
    assert read_spec(spec, run, whole, {}) is None  # no time: a program without the scope
    assert read_spec(spec, types.SimpleNamespace(device={"kind": "cpu", "count": 1}), whole,
                     {"lfm2_short_conv_ms": 5.0}) is None  # no peak: never an assumed one


# What ``BENCHMARK.json`` held at the parent commit (30533df), by digest of each
# list as ``json.dumps(..., sort_keys=True)`` gives it: this PR adds entries
# after them and changes none. (``test_fourth_cell.py`` holds the same of a
# toy cell; ``test_relative_counts.py`` says what became of its literal count.)
AT_THE_PARENT = {
    "configs": (2, "0a85ab09a7734b74f2d7ee60fa1435688f1383b1c554d2221c3fe17ce46e8820"),
    "workloads": (3, "82898b5e88b8c84103db9828012204568bbfbf4be3c2a16c548fbb852eae6e26"),
    "per_layer": (40, "9aec128b95a423240ac43bbdf9c08f50d64ea5fe6d79313e38d4b6e41911225d"),
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
    assert m["configs"][2]["name"] == "lfm2-24b-a2b-d5"
    assert m["workloads"][3]["name"] == CELL_NAME and m["workloads"][3]["chips"] == 1
    assert [e["name"] for e in m["per_layer"][40:50]] == list(OWN)
    # the metrics every cell reports are the 33 without a ``workloads`` list;
    # then Trinity's seven and this cell's ten
    assert sum("workloads" not in e for e in m["per_layer"][:50]) == 33
    listed = [tuple(e["workloads"]) for e in m["per_layer"][:50] if "workloads" in e]
    assert listed == [("trinity-mini-d5.steps",)] * 7 + [(CELL_NAME,)] * 10
