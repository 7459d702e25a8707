"""The numbers ``keye-vl-2-30b-a3b-txt-d4`` brings: its ``flops`` group and its
parameter count against independent counts from the source's keys, the picked
pairs' and the index scores' operations and bytes against counts by hand, its
``kernels`` group, and the readers of the cell's own twelve per-layer metrics
on one recorded step of the cell (``data/recorded_keye_vl2/``)."""

from __future__ import annotations

import json
import types

import pytest

from perfbench import dsa_counts, flops, kernel_counts, manifest
from perfbench.readers import kernel_roofline, keye_vl2_roofline, read_spec

from perfbench_helpers import DATA as FIXTURES, REPO

CELL_NAME = "keye-vl-2-30b-a3b-txt-d4.steps"
CONFIG = json.loads((REPO / "perfbench" / "configs" / "keye-vl-2-30b-a3b-txt-d4.json").read_text())
TRAFFIC = json.loads((REPO / "perfbench" / "traffic" / f"{CELL_NAME}.json").read_text())
# The metrics only this cell reports, with their specs, in the manifest's order.
OWN = {e["name"]: (e, json.loads((REPO / "perfbench" / "layer_metrics" / f"{e['name']}.json").read_text()))
       for e in manifest.load_manifest(REPO)["per_layer"] if e.get("workloads") == [CELL_NAME]}
SPECS = {name: spec for name, (_, spec) in OWN.items()}
RECORDED = FIXTURES / "recorded_keye_vl2"


def sizes():
    c, sa = CONFIG, CONFIG["sa_config"]
    d, hd = c["hidden_size"], c["head_dim"]
    return (d, c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd,
            sa["indexer_num_heads"] * sa["indexer_head_dim"], sa["indexer_head_dim"], sa["indexer_num_heads"],
            c["moe_intermediate_size"], c["share"]["experts_routed"])


def test_the_flops_group_gives_the_count_from_the_sources_keys():
    c, s = CONFIG, TRAFFIC["sequence"]
    d, q, kv, iq, ik, ih, f, experts = sizes()
    attention = 2 * d * q + 2 * d * kv
    indexer = d * iq + d * ik + d * ih
    pairs = c["num_experts_per_tok"] * c["num_experts"] / experts  # a token's choices that are held here
    routed = d * experts + pairs * 3 * d * f
    assert (attention, indexer, pairs, routed) == (18_874_368, 2_260_992, 1.0, 262_144 + 4_718_592)
    active = 4 * (attention + indexer + routed) + c["vocab_size"] * d
    assert active == 143_360_000 == flops.matmul_params(c["flops"])
    assert c["flops"]["mlp_width"] * d == indexer + routed == 7_241_728
    topk = c["sa_config"]["topk"]
    assert c["flops"]["attention_keys"] == [topk] * 4  # a query is counted with the keys the model gives it
    mine = 6 * active + 12 * q * 4 * min(s, topk)
    assert mine == flops.flops_per_token(c["flops"], s)
    if s == 16384:
        assert mine == 1_262_813_184
    # what is left out though computed: the score products and the KL's pass over the causal triangle
    left = (dsa_counts.index_scores(1, s, ih, ik, 4)["flops"] * 3 + 4 * 2.0 * q * (s * (s + 1) // 2)) / s
    assert "LEFT OUT" in c["flops_why"] and 0.15 < left / mine < 0.45
    # the formula with every layer full and the causal triangle walked would count 8 times the attention
    whole = dict(c["flops"], attention_keys=[None] * 4)
    assert flops.flops_per_token(whole, s) - mine == 12 * q * 4 * (s - topk)


def test_the_parameters_are_the_issues_arithmetic_and_the_compiles_state():
    c = CONFIG
    d, q, kv, iq, ik, ih, f, experts = sizes()
    attention = 2 * d * q + 2 * d * kv + 2 * c["head_dim"]
    indexer = d * iq + d * ik + d * ih + 2 * ik
    block = attention + indexer + d * experts + c["num_experts"] * 3 * d * f + 2 * d
    assert (attention, indexer, block) == (18_874_624, 2_261_120, 96_899_456)
    total = 4 * block + 2 * c["vocab_size"] * d + d  # untied: twice
    recorded = json.loads((FIXTURES / "parameters" / "keye-vl-2-30b-a3b-txt-d4.json").read_text())
    assert total == 465_391_104 == recorded["parameters"]
    assert 10.23e9 < 22 * total < 10.25e9 and 1.86e9 < 4 * total < 1.87e9
    assert 4 * (block - 8 * 3 * d * f) + 2 * c["vocab_size"] * d + d == 314_396_160  # eight held, the ladder's fallback


def test_the_state_the_step_compiles_over_holds_that_many_parameters():
    """The program's own count at the cell's sizes, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import build_model

    conf = {}
    for s in CONFIG["job_sets"][1:]:
        key, value = s.removeprefix("job.model_config.").split("=", 1)
        conf[key] = json.loads(value)
    model, cfg = build_model({"family": "keye_vl2", "config": conf})
    assert cfg.layer_types == ("sparse_attention",) * 4 + ("experts",) * 4
    variables = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 64), jnp.int32))
    leaves = jax.tree.leaves(variables["params"])
    assert (sum(x.size for x in leaves), len(leaves)) == (465_391_104, 71)
    assert sum(x.size for x in jax.tree.leaves(variables["moe_state"])) == 4 * 128  # beside them


def test_the_picked_pairs_and_the_selected_attentions_count_by_hand():
    # 6 positions, 3 keys a query: 1 + 2 + 3 + 3 + 3 + 3 pairs
    assert dsa_counts.picked_pairs(6, 3) == 15 and dsa_counts.picked_pairs(6, 9) == 21 == 6 * 7 // 2
    # one layer, 2 query heads to 1 key head of 4: 14 x 4 a pair and head; q 6 x 2 x 4 x 2 B = 96, kv 48;
    # (2 q + 2 kv) forward, (4 q + 4 kv) backward, and the selection's bit a causal pair read three times
    c = dsa_counts.flash_attention_selected(1, 6, 2, 1, 4, 3, 1, element_bytes=2)
    assert c == {"flops": 14.0 * 4 * 2 * 15, "bytes": 6 * 96 + 6 * 48 + 3 * 21 / 8}
    band = kernel_counts.flash_attention(1, 6, 2, 1, 4, 3)  # a window of 3 holds the same number of pairs
    assert c["flops"] == band["flops"] and c["bytes"] - band["bytes"] == 3 * 21 / 8
    cell = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    step = keye_vl2_roofline.counts("flash_attention_selected", None, cell)
    s = TRAFFIC["sequence"]
    if s == 16384:
        pairs = 2048 * 2049 // 2 + (16384 - 2048) * 2048
        assert pairs == 31_458_304 and round(1e4 * pairs / (s * (s + 1) // 2)) == 2344  # 23.4 % of the causal pairs
        assert step["flops"] == 4 * 14 * 128 * 32 * pairs and 7.2e12 < step["flops"] < 7.3e12  # 36.6 ms at the peak
        walked = 4 * kernel_counts.flash_attention(1, s, 32, 4, 128, None)["flops"]  # the triangle the kernels walk
        assert 4.2 < walked / step["flops"] < 4.3  # so the share can read 23 % at the very most
    assert set(kernel_roofline.KERNELS) == {"flash_attention_window", "grouped_swiglu"}  # left alone


def test_the_index_scores_count_by_hand():
    # one layer, 5 positions, 3 index heads of 2: 15 causal pairs, 2 x 2 a pair and head; read: queries 5 x 3 x 2,
    # one key head 5 x 2 (elements of 2 B) and the head weights 5 x 3 of 4 B; written: a bit a causal pair
    c = dsa_counts.index_scores(1, 5, 3, 2, 1, element_bytes=2)
    assert c == {"flops": 2.0 * 2 * 3 * 15, "bytes": 5 * (12 + 4 + 12) + 15 / 8}
    cell = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    step = keye_vl2_roofline.counts("index_scores", None, cell)
    s = TRAFFIC["sequence"]
    assert step["flops"] == 4 * 2 * 64 * 16 * (s * (s + 1) // 2)
    if s == 16384:
        assert 1.09e12 < step["flops"] < 1.11e12  # 5.6 ms a step at the peak; the bytes' bound is far smaller
        assert step["flops"] / 197e12 > 20 * step["bytes"] / 819e9


def test_the_kernels_group_is_the_count_from_the_sources_keys():
    c, k, sa = CONFIG, CONFIG["kernels"], CONFIG["sa_config"]
    assert k["flash_attention_selected"] == {
        "layers": c["num_hidden_layers"], "heads": c["num_attention_heads"], "kv_heads": c["num_key_value_heads"],
        "head_size": c["head_dim"], "topk": sa["topk"]}
    assert k["index_scores"] == {"layers": c["num_hidden_layers"], "heads": sa["indexer_num_heads"],
                                 "head_size": sa["indexer_head_dim"], "topk": sa["topk"]}
    assert k["grouped_swiglu"] == {"layers": c["num_hidden_layers"], "width": c["hidden_size"],
                                   "expert_width": c["moe_intermediate_size"], "held": c["num_experts"]}
    assert len(c["kernels_why"]) > 100 and len(c["flops_why"]) > 100


@pytest.fixture(scope="module")
def recorded():
    """One traced step of the cell (the mix says 1 step here), and the
    routing and objective lines as the worker words them, from that step's own
    counters (101423 pairs, the probe's fourth step)."""
    cell = types.SimpleNamespace(config=CONFIG, traffic=dict(TRAFFIC, inner_steps=1))
    lines = (RECORDED / "round_lines.txt").read_text()
    run = types.SimpleNamespace(out_dir=RECORDED, texts={"w0": lines}, measured=[{"round": 0}],
                                device={"kind": "TPU v5 lite", "count": 1})
    return cell, run


@pytest.fixture(scope="module")
def with_the_cell():
    return REPO, manifest.load_manifest(REPO)


def test_the_cells_own_twelve_are_listed_and_their_specs_name_readers_that_exist(with_the_cell):
    assert list(OWN) == [
        "keye_index_scores_ms", "keye_index_scores_roofline", "keye_index_select_ms", "keye_index_kl_ms",
        "keye_flash_sel_ms", "keye_flash_sel_roofline", "keye_keys_picked_share", "keye_moe_route_ms",
        "keye_moe_experts_ms", "keye_moe_experts_roofline", "keye_moe_pairs_per_token", "keye_moe_load_max_over_mean"]
    for name, (entry, spec) in OWN.items():
        assert (spec["layer"], spec["unit"], spec["moves"]) == (entry["layer"], entry["unit"], "tokens_per_s")
        assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()
        assert name.endswith("_roofline") == (entry["unit"] == "%")
    # LFM2's specs for the routed layer, copied under this cell's names: the same readers over the same
    # scopes and fields, but for the share's reader, which reads this cell's entry of the kernels group
    for mine in ("moe_route_ms", "moe_experts_ms", "moe_pairs_per_token", "moe_load_max_over_mean"):
        theirs = json.loads((REPO / "perfbench" / "layer_metrics" / f"lfm2_{mine}.json").read_text())
        assert SPECS[f"keye_{mine}"] == theirs
    assert SPECS["keye_moe_experts_roofline"]["kernel"] == "grouped_swiglu"
    assert SPECS["keye_flash_sel_ms"]["names"] == ["flash_attention_sel.", "flash_attention_bwd_sel."]
    # the 33 that every cell reports, and the twelve; no other cell reports these
    root, m = with_the_cell
    cell, other = manifest.resolve(CELL_NAME, root, m), manifest.resolve("trinity-mini-d5.steps", root, m)
    assert len(cell.per_layer) == 33 + len(OWN) == 45
    assert not set(OWN) & {e["name"] for e, _ in other.per_layer}
    assert [e["name"] for e in cell.end_to_end] == ["tokens_per_s", "sync_exposed_s", "setup_s"]


def test_the_twelve_metrics_read_the_recorded_step(recorded):
    cell, run = recorded
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, run, cell, values)
    assert all(v is not None for v in values.values()), values
    expect = json.loads((RECORDED / "readings.json").read_text())
    assert {k: repr(v) for k, v in values.items()} == expect  # digit for digit what the readers gave
    for share in ("keye_index_scores_roofline", "keye_flash_sel_roofline", "keye_moe_experts_roofline"):
        assert 0 < values[share] < 100, (share, values[share])
    # the kernels walk the whole causal triangle under the mask and are counted by the picked pairs
    assert values["keye_flash_sel_roofline"] < 23.5
    assert abs(values["keye_keys_picked_share"] - 0.2344) < 1e-4
    pairs = values["keye_moe_pairs_per_token"] * 4 * TRAFFIC["batch"] * TRAFFIC["sequence"]
    assert keye_vl2_roofline.counts("grouped_swiglu", run, cell)["flops"] == pytest.approx(
        18.0 * 2048 * 768 * pairs, rel=1e-3)


def test_the_recorded_step_has_one_set_of_three_kernels_a_layer_and_the_scopes_apart(recorded):
    from perfbench.readers import device_scope

    _, run = recorded
    events = device_scope.device_events(device_scope.load(run.out_dir))
    kernels = sorted({e["name"].split(".")[0] for e in events if e["name"].startswith("flash_attention")})
    assert kernels == ["flash_attention_bwd_sel", "flash_attention_sel"]  # no unselected call in this model
    spec = SPECS["keye_flash_sel_ms"]
    assert sum(device_scope.matches(e, spec["scopes"], spec["names"]) for e in events) == 4 * 3
    inside = lambda scope: [e for e in events if device_scope.matches(e, [scope], [])]
    assert inside("index_scores") and inside("index_select") and inside("index_kl")
    # the walk's own score products are the walk's: no event under both scopes
    assert not [e for e in inside("index_kl") if device_scope.matches(e, ["index_scores"], [])]
    assert all("sparse_attention" in e["args"]["tf_op"] for e in inside("index_select"))


def test_with_no_trace_and_on_a_program_without_the_scopes_the_readers_return_nothing(recorded, tmp_path):
    """What the parent commit gives under this PR's benchmark files: no
    scope, no routing line, no kernel of this family, and no reader raises."""
    cell, run = recorded
    gone = types.SimpleNamespace(**{**vars(run), "out_dir": tmp_path, "texts": {"w0": ""}})
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, gone, cell, values)
    assert set(values.values()) == {None}


@pytest.mark.parametrize("kernel,time_ms", [("flash_attention_selected", "keye_flash_sel_ms"),
                                           ("index_scores", "keye_index_scores_ms"),
                                           ("grouped_swiglu", "keye_moe_experts_ms")])
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


# What ``BENCHMARK.json`` held at the parent commit (096803d), by digest of each
# list as ``json.dumps(..., sort_keys=True)`` gives it: PR 50's entries go after these.
AT_THE_PARENT = {
    "configs": (5, "19548e57a418c5d4157a656734276b0b913b27222c1d01488db2bfa4c5c9d221"),
    "workloads": (6, "1b49830edce2fa4355a7456a3556227d93c24394371c6def7cae9794769b12d9"),
    "per_layer": (81, "6bb1fa2b15889a176a90a1150c69ac15ed8e75c2ff381913322474ce30ec92e0"),
}
THE_REST_AT_THE_PARENT = "995383cb1e2cba2874cd07d3cfdf89f7d4e022cc78631faa1a7da53661652dbe"


def _digest(obj) -> str:
    import hashlib

    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()


def test_the_cells_entries_follow_what_was_there_and_change_none_of_it(with_the_cell):
    _, m = with_the_cell
    for key, (count, digest) in AT_THE_PARENT.items():
        assert _digest(m[key][:count]) == digest, key
    assert _digest({k: m[k] for k in ("command", "paths", "run_seconds", "end_to_end")}) == THE_REST_AT_THE_PARENT
    names = lambda key: [e["name"] for e in m[key]]
    at = {key: names(key).index(first) for key, first in (
        ("configs", "keye-vl-2-30b-a3b-txt-d4"), ("workloads", CELL_NAME), ("per_layer", next(iter(OWN))))}
    assert at == {key: count for key, (count, _) in AT_THE_PARENT.items()}  # straight after the parent's
    assert names("per_layer")[at["per_layer"]:][:12] == list(OWN)
    cell = m["workloads"][at["workloads"]]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and len(m["configs"][at["configs"]]["why"]) <= 200


def test_the_mix_is_the_jobs_defaults_and_the_cell_is_held_to_its_reference():
    """The review of PR 50: no rate, no kernel's tile size and no other job
    key rides in the mix, and ``correct`` names the plain reference."""
    assert TRAFFIC["job_sets"] == []
    assert (TRAFFIC["batch"], TRAFFIC["sequence"]) == (1, 16384) and TRAFFIC["inner_steps"] % 8 == 0
    assert CONFIG["checks"]["reference"] == "keye_vl2"
    assert (REPO / "perfbench" / "reference" / "keye_vl2.py").is_file()
    # the limit lies between the sound seeds' largest reading and the float8 control's smallest, with room
    limit, read = CONFIG["checks"]["reference_tolerance"], CONFIG["checks"]["reference_readings"]
    assert len(read["sound"]) >= 12 and len(read["float8_control"]) == 4
    assert 2 * max(read["sound"]) < limit < min(read["float8_control"]) / 1.3
    assert not [s for s in CONFIG["job_sets"] if "inner_lr" in s or "moe_chunk" in s]
