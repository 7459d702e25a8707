"""The numbers ``phi-4-mini-flash-d5`` brings: its ``flops`` group and its
parameter count against independent counts from the source's keys, the scan's
bytes and operations against a count by hand, its ``kernels`` group, and the
readers of the cell's own nine per-layer metrics on one recorded step of the
cell (``data/recorded_phi4flash/``)."""

from __future__ import annotations

import json
import types

import pytest

from perfbench import flops, kernel_counts, manifest, selective_scan_counts
from perfbench.readers import kernel_roofline, phi4flash_roofline, read_spec

from perfbench_helpers import DATA as FIXTURES, REPO

CONFIG = json.loads((REPO / "perfbench" / "configs" / "phi-4-mini-flash-d5.json").read_text())
TRAFFIC = manifest.load_traffic(REPO / "perfbench" / "traffic" / "phi-4-mini-flash-d5.steps.json")
CELL_NAME = "phi-4-mini-flash-d5.steps"
# The metrics only this cell reports, with their specs, in the manifest's order.
OWN = {e["name"]: (e, s) for e, s in manifest.resolve(CELL_NAME, REPO).per_layer
       if e.get("workloads") == [CELL_NAME]}
SPECS = {name: spec for name, (_, spec) in OWN.items()}
RECORDED = FIXTURES / "recorded_phi4flash"
KINDS = ["window_attention", "mamba", "full_attention", "gmu", "cross_attention"]


def sizes():
    c = CONFIG
    d, hd, m = c["hidden_size"], c["head_dim"], c["mamba"]
    return (d, c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, m["expand"] * d,
            m["d_state"], m["dt_rank"], c["intermediate_size"])


def test_the_flops_group_gives_the_count_from_the_sources_keys():
    c, s = CONFIG, TRAFFIC["sequence"]
    d, q, kv, di, n, rank, f = sizes()
    mlp = d * 2 * f + f * d
    mamba = d * 2 * di + di * (rank + 2 * n) + rank * di + di * d
    gmu = 2 * d * di
    attention = d * (q + 2 * kv) + q * d
    cross = d * q + q * d
    assert (mlp, mamba, gmu, attention, cross) == (78_643_200, 41_123_840, 26_214_400, 19_660_800, 13_107_200)
    active = 5 * mlp + mamba + gmu + 2 * attention + cross + c["vocab_size"] * d
    assert active == 577_003_520 == flops.matmul_params(c["flops"])
    assert c["flops"]["mlp_width"] * d == active - attention - c["vocab_size"] * d == 493_322_240
    keys = {"window_attention": c["sliding_window"], "full_attention": None, "cross_attention": None}
    assert c["flops"]["attention_keys"] == [keys.get(k, 0) for k in KINDS]
    seen = c["sliding_window"] + 2 * s
    mine = 6 * active + 12 * q * seen  # one value product as wide as the queries: the second is not counted
    assert mine == flops.flops_per_token(c["flops"], s)
    if s == 8192:
        assert mine == 3_981_066_240
    # what the differential form computes on top (a value of twice the head size), left out
    assert "NOT counted" in c["flops_why"] and 12 * q * seen / mine < 0.14
    # the formula with this model's five layers as attention layers would read too high
    naive = dict(c["flops"], layers=5, mlp_width=2 * f + f, mlp_matrices=1)
    del naive["attention_keys"]
    assert 1.05 < flops.flops_per_token(naive, s) / mine < 1.5


def test_the_parameters_are_the_issues_arithmetic_and_the_compiles_state():
    c = CONFIG
    d, q, kv, di, n, rank, f = sizes()
    hd, taps = c["head_dim"], c["mamba"]["d_conv"]
    norms, mlp = 4 * d, d * 2 * f + f * d
    mamba = d * 2 * di + taps * di + di + di * (rank + 2 * n) + rank * di + di + di * n + di + di * d
    attention = d * (q + 2 * kv) + (q + 2 * kv) + q * d + d + 4 * hd + 2 * hd
    gmu = 2 * d * di
    cross = d * q + q + q * d + d + 4 * hd + 2 * hd
    assert (norms, mamba, attention, gmu, cross) == (10_240, 41_241_600, 19_668_864, 26_214_400, 13_112_704)
    total = 5 * (norms + mlp) + mamba + 2 * attention + gmu + cross + 2 * d + c["vocab_size"] * d  # tied: once
    recorded = json.loads((FIXTURES / "parameters" / "phi-4-mini-flash-d5.json").read_text())
    assert total == 577_199_232 == recorded["parameters"]  # the AOT compile's state (PERF.md 4)
    assert 12.69e9 < 22 * total < 12.71e9 and 2.30e9 < 4 * total < 2.32e9
    assert 11.5e9 < 1.25 * (16 * total + 17e6) < 11.6e9  # what the floor's rule asks of /dev/shm


def test_the_state_the_step_compiles_over_holds_that_many_parameters():
    """The program's own count at the cell's sizes, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import build_model

    conf = {}
    for s in CONFIG["job_sets"][1:]:
        key, value = s.removeprefix("job.model_config.").split("=", 1)
        conf[key] = json.loads(value)
    model, cfg = build_model({"family": "phi4flash", "config": conf})
    assert list(cfg.layer_types) == KINDS
    variables = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 64), jnp.int32))
    leaves = jax.tree.leaves(variables["params"])
    assert (sum(x.size for x in leaves), len(leaves)) == (577_199_232, 71) and list(variables) == ["params"]


def test_the_scans_count_by_hand():
    # one layer, 3 positions, 2 channels, 4 states. Forward x, dt in (2 each), B, C in (4
    # each), y out (2): 12; backward the four again and dy (14) and the four's gradients
    # (12): 38 elements a position = 8 x 2 + 6 x 4 + ... of 2 B; 30 operations a state
    c = selective_scan_counts.selective_scan(1, 3, 2, 4, 1, element_bytes=2)
    assert c == {"flops": 3.0 * 2 * 4 * 30, "bytes": 3.0 * (8 * 2 + 6 * 4) * 2}
    cell = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    step = phi4flash_roofline.counts("selective_scan", cell)
    assert set(kernel_roofline.KERNELS) == {"flash_attention_window", "grouped_swiglu"}  # left alone
    tokens = TRAFFIC["batch"] * TRAFFIC["sequence"]
    assert step["bytes"] == tokens * (8 * 5120 + 6 * 16) * 2
    # bound by the memory's peak of the two a roofline has: 0.82 ms a sequence of 8192
    assert step["bytes"] / 819e9 > 5 * step["flops"] / 197e12
    assert 1e3 * 8192 * (8 * 5120 + 6 * 16) * 2 / 819e9 == pytest.approx(0.82, abs=0.01)
    # the states of every position, which no scan here may write: 2.7 GB a sequence
    assert 8192 * 5120 * 16 * 4 == 2_684_354_560 > 3 * step["bytes"] / tokens * 8192


def test_the_kernels_group_is_the_count_from_the_sources_keys():
    c, k = CONFIG, CONFIG["kernels"]
    grouping = {"heads": c["num_attention_heads"], "kv_heads": c["num_key_value_heads"],
                "head_size": c["head_dim"]}
    calls = 2  # a value of twice the head size: [v1, v1], then [v2, v2]
    assert k["flash_attention_window"] == {
        "layers": calls * KINDS.count("window_attention"), **grouping, "window": c["sliding_window"]}
    assert k["flash_attention_full"] == {
        "layers": calls * (KINDS.count("full_attention") + KINDS.count("cross_attention")), **grouping,
        "window": None}
    assert k["selective_scan"] == {"layers": KINDS.count("mamba"), "width": 2 * c["hidden_size"],
                                   "state": c["mamba"]["d_state"]}
    assert len(c["kernels_why"]) > 100 and len(c["flops_why"]) > 100
    # a call over the whole triangle at 40 heads of 64: 1.2 TFLOP a sequence of 8192, 6.1 ms at the peak
    full = kernel_counts.flash_attention(1, 8192, 40, 20, 64, None)
    assert full["flops"] == 14 * 64 * 40 * (8192 * 8193 // 2) and 1.19e12 < full["flops"] < 1.21e12
    band = kernel_counts.flash_attention(1, 8192, 40, 20, 64, 512)
    assert band["flops"] == 14 * 64 * 40 * (512 * 513 // 2 + (8192 - 512) * 512)
    assert 7.9 < full["flops"] / band["flops"] < 8.3  # the band is an eighth of the triangle


@pytest.fixture(scope="module")
def recorded():
    """One traced step of the cell (the mix says 1 step here)."""
    cell = types.SimpleNamespace(config=CONFIG, traffic=dict(TRAFFIC, inner_steps=1))
    run = types.SimpleNamespace(out_dir=RECORDED, texts={"w0": ""}, measured=[{"round": 1}],
                                device={"kind": "TPU v5 lite", "count": 1})
    return cell, run


def test_the_cells_own_nine_are_listed_and_their_specs_name_readers_that_exist():
    assert list(OWN) == [
        "phi4_mamba_ms", "phi4_scan_ms", "phi4_scan_roofline", "phi4_gmu_ms", "phi4_flash_window_ms",
        "phi4_flash_window_roofline", "phi4_flash_full_ms", "phi4_flash_full_roofline",
        "phi4_diff_combine_ms"]
    for name, (entry, spec) in OWN.items():
        assert (spec["layer"], spec["unit"], spec["moves"]) == (entry["layer"], entry["unit"], "tokens_per_s")
        assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()
        assert name.endswith("_roofline") == (entry["unit"] == "%")
    # Trinity's two window specs, copied under this cell's names: the same reader over the same names
    for mine in ("flash_window_ms", "flash_window_roofline"):
        theirs = json.loads((REPO / "perfbench" / "layer_metrics" / f"{mine}.json").read_text())
        if "time_ms" in theirs:
            theirs["time_ms"] = {"metric": "phi4_flash_window_ms"}
        assert SPECS[f"phi4_{mine}"] == theirs
    # 33 that every cell reports, and the nine; no other cell reports these
    cell, other = manifest.resolve(CELL_NAME, REPO), manifest.resolve("lfm2-24b-a2b-d5.steps", REPO)
    assert len(cell.per_layer) == 33 + len(OWN) == 42
    assert not set(OWN) & {e["name"] for e, _ in other.per_layer}
    assert [e["name"] for e in cell.end_to_end] == ["tokens_per_s", "sync_exposed_s", "setup_s"]


def test_the_nine_metrics_read_the_recorded_step(recorded):
    cell, run = recorded
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, run, cell, values)
    assert all(v is not None for v in values.values()), values
    expect = json.loads((RECORDED / "readings.json").read_text())
    assert {k: repr(v) for k, v in values.items()} == expect  # digit for digit what the readers gave
    # the scan is inside the Mamba layer's mixer, whose projections are the rest of it
    assert 0 < values["phi4_scan_ms"] < values["phi4_mamba_ms"]
    # the band is an eighth of a triangle and there are half as many calls over it
    assert 4 * values["phi4_flash_window_ms"] < values["phi4_flash_full_ms"]
    for share in ("phi4_scan_roofline", "phi4_flash_window_roofline", "phi4_flash_full_roofline"):
        assert 0 < values[share] < 100, (share, values[share])
    assert values["phi4_scan_roofline"] < 10  # bound by the vector unit, which the roofline has no peak for
    assert phi4flash_roofline.counts("flash_attention_full", cell) == {
        name: 4 * TRAFFIC["batch"] * v
        for name, v in kernel_counts.flash_attention(1, TRAFFIC["sequence"], 40, 20, 64, None).items()}


def test_the_names_tell_the_window_calls_from_the_calls_over_the_whole_triangle(recorded):
    from perfbench.readers import device_scope

    _, run = recorded
    events = device_scope.device_events(device_scope.load(run.out_dir))
    kernels = sorted({e["name"].split(".")[0] for e in events if e["name"].startswith("flash_attention")})
    assert kernels == ["flash_attention", "flash_attention_bwd", "flash_attention_bwd_w512", "flash_attention_w512"]
    count = lambda spec: sum(device_scope.matches(e, spec["scopes"], spec["names"]) for e in events)
    # forward one kernel a call, backward two: 2 calls over the band, 4 over the triangle
    assert count(SPECS["phi4_flash_window_ms"]) == 2 * 3 and count(SPECS["phi4_flash_full_ms"]) == 4 * 3


def test_with_no_trace_and_on_a_program_without_the_scopes_the_readers_return_nothing(recorded, tmp_path):
    """What the parent commit gives under this PR's benchmark files: no
    scope, no kernel of this family, and no reader raises."""
    cell, run = recorded
    gone = types.SimpleNamespace(**{**vars(run), "out_dir": tmp_path})
    values: dict = {}
    for name, spec in SPECS.items():
        values[name] = read_spec(spec, gone, cell, values)
    assert set(values.values()) == {None}


@pytest.mark.parametrize("kernel,time_ms", [("selective_scan", "phi4_scan_ms"),
                                           ("flash_attention_full", "phi4_flash_full_ms")])
@pytest.mark.parametrize("missing", ["the_group", "the_entry", "a_key_of_the_entry"])
def test_a_configuration_without_the_entry_reads_nothing_and_says_why(capsys, missing, kernel, time_ms):
    config = {k: v for k, v in CONFIG.items() if k != "kernels"}
    if missing == "the_entry":
        config["kernels"] = {"flash_attention_window": CONFIG["kernels"]["flash_attention_window"]}
    elif missing == "a_key_of_the_entry":
        config["kernels"] = {kernel: {k: v for k, v in CONFIG["kernels"][kernel].items() if k != "layers"}}
    bare = types.SimpleNamespace(config=config, traffic=TRAFFIC)
    run = types.SimpleNamespace(device={"kind": "TPU v5 lite", "count": 1})
    spec = next(s for s in SPECS.values() if s.get("kernel") == kernel)
    assert read_spec(spec, run, bare, {time_ms: 50.0}) is None
    said = capsys.readouterr().err
    assert said.startswith("perfbench: ") and ("layers" if missing == "a_key_of_the_entry" else kernel) in said
    whole = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    assert read_spec(spec, run, whole, {time_ms: 50.0}) > 0
    assert read_spec(spec, run, whole, {}) is None  # no time: a program without the scope
    assert read_spec(spec, types.SimpleNamespace(device={"kind": "cpu", "count": 1}), whole,
                     {time_ms: 50.0}) is None  # no peak: never an assumed one


# What ``BENCHMARK.json`` held at the parent commit (e8de834), by digest of each
# list as ``json.dumps(..., sort_keys=True)`` gives it: this PR adds entries
# after them and changes none.
AT_THE_PARENT = {
    "configs": (3, "e6b0abbc546c6514a88e05d3585eb1b39883bcba0f812f641c66d3e9e00bc595"),
    "workloads": (4, "fca0d607c6307d8a0e30377351054e8681a317275267dd9fce1635fc60f2a4d6"),
    "per_layer": (62, "a37ffd76ce21ecf3ed296652f8b35c547be3e3a9d7c696fb3c1414cac70aff74"),
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
    assert m["configs"][3]["name"] == "phi-4-mini-flash-d5"
    assert m["workloads"][4]["name"] == CELL_NAME and m["workloads"][4]["chips"] == 1
    assert [e["name"] for e in m["per_layer"][62:71]] == list(OWN)
    assert all(len(w["why"]) <= 200 for w in m["workloads"]) and all(len(c["why"]) <= 200 for c in m["configs"])


def test_the_twelve_of_the_journeys_stand_where_they_stood_and_list_what_they_listed():
    """``test_journey_metrics.py`` holds this of the manifest's *last* twelve of
    62 entries, which this cell's nine, appended, outdate (``tests/conftest.py``
    expects that one line to fail): every assertion of it again, by position."""
    from test_journey_metrics import CELLS, TWELVE

    per_layer = manifest.load_manifest(REPO)["per_layer"]
    assert [e["name"] for e in per_layer[50:62]] == TWELVE
    for e in per_layer[50:62]:
        assert e["workloads"] == CELLS and e["moves"] == "sync_exposed_s" and e["better"] == "lower"
    assert not set(TWELVE) & set(OWN)
