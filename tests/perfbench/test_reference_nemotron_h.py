"""The plain reference of the nemotron_h configurations
(``perfbench/reference/nemotron_h.py``) against the program at a small size on
the CPU in float32, with one rank's share of the experts and a selection bias
that is not zero: the seeded weights to an ulp, the logits, the loss and the
gradient of every leaf; and each of ten faults planted in a copy of the
reference leaves the program by more than the tolerance."""

from __future__ import annotations

import json
import sys
import types

import numpy as np
import pytest

from perfbench_helpers import REPO

PATTERN = "EMEM*EM"  # the small source: seven layers, of which 1 to 5 are run: M E M * E
SMALL = dict(  # the reference's keys (the source's names) ...
    hidden_size=64, hybrid_override_pattern=PATTERN, layers_run=[1, 2, 3, 4, 5], num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=1, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, n_routed_experts=4, share={"experts_routed": 16, "expert_offset": 8},
    num_experts_per_tok=3, moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    norm_topk_prob=True, routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
    rescale_prenorm_residual=True, vocab_size=256,
)
PROGRAM = dict(  # ... and the same stack in the program's
    vocab_size=256, hidden_size=64, pattern=PATTERN, layers_run=[1, 2, 3, 4, 5], num_heads=4,
    num_kv_heads=1, head_dim=16, mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    num_experts=16, experts_held=4, expert_offset=8, experts_per_token=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=48, dtype="float32", moe_chunk=64,
)
KINDS = ["mamba2", "experts", "mamba2", "full_attention", "experts"]
SEED = 2147485132 % 2**31
TOLERANCE = 2e-5  # float32 against float32: the sound difference is 1e-6 here


def program(ids):
    """The ``nemotron_h`` family module as the worker builds and seeds it."""
    from hypha_tpu.executor import training

    spec = {"family": "nemotron_h", "config": PROGRAM, "seed": SEED, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    model, variables, causal_lm, _ = training._init_model(
        cfg, None, "/nonexistent", {"input_ids": ids})
    assert causal_lm and sorted(variables) == ["moe_state", "params"]
    return model, variables


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(0, 256, (2, 160)).astype(np.int32)


@pytest.fixture(scope="module")
def worker(ids):
    return program(ids)


@pytest.fixture(scope="module")
def biased(worker):
    """The reference's weights and the program's ``moe_state``, with a
    selection bias that is not zero, the same on both sides."""
    import jax.numpy as jnp

    from hypha_tpu.models.routed import STATE
    from perfbench.reference import nemotron_h

    _, variables = worker
    w, rng, state = nemotron_h.weights(SMALL, SEED), np.random.default_rng(17), {}
    for layer in sorted(variables[STATE], key=lambda n: int(n.split("_")[1])):
        j = int(layer.split("_")[1])
        assert float(jnp.abs(w[f"{j}.bias"]).max()) == 0.0  # zero as at the first step
        b = jnp.asarray(rng.normal(0, 0.1, 16), jnp.float32)
        state[layer], w[f"{j}.bias"] = {"mixer": {"expert_bias": b}}, b
    return w, {STATE: state}


def program_loss_and_grads(model, variables, extras, ids):
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import chunked_causal_ce

    body = model.clone(with_head=False)

    def loss(params):
        hidden, _ = body.apply({"params": params, **extras}, jnp.asarray(ids))
        return chunked_causal_ce(hidden[:, :-1], params[model.head_leaf], jnp.asarray(ids)[:, 1:], chunk=32)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(variables["params"])


def reference_loss(module, w, ids, config, operands=None):
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        total = sum(module.sequence_nll(w, jnp.asarray(row), config, operands) for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))


def at(tree, path):
    for part in path:
        tree = tree[part]
    return tree


def test_the_reference_gives_each_layer_its_part_by_the_patterns_letter():
    from perfbench.reference import nemotron_h

    assert nemotron_h.kinds(SMALL) == KINDS
    published = {"hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    kinds = nemotron_h.kinds(published)
    assert len(kinds) == 52 and [kinds.count(k) for k in ("mamba2", "experts", "full_attention")] == [23, 23, 6]
    assert nemotron_h.kinds({**published, "layers_run": list(range(7))}) == [
        "mamba2", "experts", "mamba2", "experts", "mamba2", "full_attention", "experts"]
    assert nemotron_h.routed(SMALL) == (16, 4, 8)


def test_the_reference_makes_the_workers_seeded_weights_without_the_worker(worker):
    import jax

    from perfbench.reference import nemotron_h

    _, variables = worker
    mine, spec = nemotron_h.weights(SMALL, SEED), nemotron_h.table(SMALL)
    # embedding, head, final norm (3); a norm a block (5); Mamba-2 8 each, attention 4, experts 5 each
    assert len(jax.tree_util.tree_leaves(variables["params"])) == len(spec) == 3 + 5 + 2 * 8 + 4 + 2 * 5
    for name, (path, _, shape, _) in spec.items():
        theirs = at(variables["params"], path)
        assert theirs.shape == shape == mine[name].shape, name
        np.testing.assert_allclose(mine[name], theirs, rtol=5e-7, atol=1e-8, err_msg=name)  # an ulp or two: made under jit here
    # Mamba-2's published initialisers: a step in [0.001, 0.1], A = -(1 ... heads), D ones
    step = np.log1p(np.exp(np.asarray(mine["0.dt_bias"])))
    assert 0.001 <= step.min() < step.max() <= 0.1 and step.shape == (8,)
    np.testing.assert_allclose(np.exp(np.asarray(mine["0.a_log"])), np.arange(1.0, 9.0), rtol=1e-6)
    assert float(mine["0.d"].min()) == 1.0
    # a part's output projection is drawn 1 / sqrt(7) smaller than its input projection's rule gives
    for out, fan_in in (("0.out", 64), ("3.o", 64), ("1.down", 48), ("1.experts_down", 32)):
        assert float(mine[out].std()) == pytest.approx((1 / fan_in / 7) ** 0.5, rel=0.12), out
    assert float(mine["1.up"].std()) == pytest.approx((1 / 64) ** 0.5, rel=0.12)


def test_the_reference_imports_nothing_of_the_program():
    src = (REPO / "perfbench" / "reference" / "nemotron_h.py").read_text()
    code = [x for x in src.split('"""', 2)[2].splitlines() if x.lstrip().startswith(("import ", "from "))]
    assert code and not any("hypha" in x or "flax" in x or "perfbench" in x for x in code), code
    assert "lax.scan(position" in src and "chunk" not in src.split('"""', 2)[2].replace("chunked", "")


def test_logits_loss_and_every_leafs_gradient_are_the_programs(worker, biased, ids):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import nemotron_h

    model, variables = worker
    w, extras = biased
    with jax.default_matmul_precision("highest"):
        logits, stats = model.apply({"params": variables["params"], **extras}, jnp.asarray(ids))
        theirs = nemotron_h.hidden(w, jnp.asarray(ids[0]), SMALL) @ w["head"].T
    np.testing.assert_allclose(logits[0], theirs, atol=3e-5)
    # the share: some pairs are held here and some elsewhere, and none is dropped
    assert 0 < int(stats["pairs_routed"].sum()) < 2 * ids.size * 3
    assert int(stats["pairs_routed"].sum()) == int(stats["pairs_computed"].sum())
    loss, grads = program_loss_and_grads(model, variables, extras, ids)
    ref, ref_grads = jax.value_and_grad(lambda w: reference_loss(nemotron_h, w, ids, SMALL))(w)
    assert abs(float(loss) - float(ref)) < TOLERANCE
    assert 5.0 < float(ref) < 6.5  # ln 256 and the head's spread
    for name, (path, *_rest) in nemotron_h.table(SMALL).items():
        scale = float(jnp.abs(ref_grads[name]).max())
        off = float(jnp.abs(at(grads, path) - ref_grads[name]).max())
        assert scale > 0 and off < 2e-3 * scale + 1e-7, (name, off, scale)


# fault -> (the reference's own line, the line a copy is given instead)
FAULTS = {
    "the_gate_applied_after_the_norm": (
        "    gated = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, g, di // g)\n"
        "    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + c[\"layer_norm_epsilon\"])\n",
        "    gated = y.reshape(s, g, di // g)\n"
        "    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + c[\"layer_norm_epsilon\"])\n"
        "    normed = normed * jax.nn.silu(z).reshape(s, g, di // g)\n"),
    "the_norm_over_all_channels_and_not_a_groups": (
        "gated = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, g, di // g)",
        "gated = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, 1, di)"),
    "b_and_c_taken_from_the_wrong_group": (
        "group_of = jnp.arange(heads) // (heads // g)", "group_of = jnp.arange(heads) % g"),
    "the_D_term_dropped": ('y = y + w[f"{j}.d"][:, None] * x', "y = y"),
    "softplus_dropped_from_the_step": (
        'dt = jax.nn.softplus(dt + w[f"{j}.dt_bias"])', 'dt = dt + w[f"{j}.dt_bias"]'),
    "the_step_left_out_of_the_input_term": (
        "+ (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]", "+ x_t[:, :, None] * b_t[:, None, :]"),
    "relu_not_squared": (
        "return mm(jnp.square(jax.nn.relu(mm(u, up))), down)", "return mm(jax.nn.relu(mm(u, up)), down)"),
    "the_routed_scaling_factor_dropped": ('return idx, wt * c["routed_scaling_factor"]', "return idx, wt"),
    "the_chosen_weights_taken_with_the_bias": (
        "wt = jnp.take_along_axis(scores, idx, axis=-1)",
        'wt = jnp.take_along_axis(scores + w[f"{j}.bias"], idx, axis=-1)'),
    # the expert part reads what the mixer before it read: one block, one input, two parts
    "a_block_given_two_parts": (
        'u = _rms(h, w[f"{j}.norm"], eps)',
        'u = _rms(h - out if j and what == "experts" else h, w[f"{j}.norm"], eps)'),
}


def a_copy_with(fault: str):
    """The reference's source with one line made wrong, as a module of its own."""
    src = (REPO / "perfbench" / "reference" / "nemotron_h.py").read_text()
    right, wrong = FAULTS[fault]
    assert src.count(right) == 1, f"the reference no longer has the line {right!r}"
    module = types.ModuleType(f"nemotron_h_with_{fault}")
    sys.modules[module.__name__] = module  # dataclasses and jit look a module up by name
    exec(compile(src.replace(right, wrong), module.__name__, "exec"), module.__dict__)
    return module


@pytest.fixture(scope="module")
def sound(worker, biased, ids):
    from perfbench.reference import nemotron_h

    w, extras = biased
    ref = float(reference_loss(nemotron_h, w, ids, SMALL))
    loss, _ = program_loss_and_grads(*worker, extras, ids)
    assert abs(float(loss) - ref) < TOLERANCE
    return float(loss)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_planted_in_a_copy_leaves_the_program_by_more_than_the_tolerance(fault, biased, ids, sound):
    wrong = float(reference_loss(a_copy_with(fault), biased[0], ids, SMALL))
    assert not abs(wrong - sound) <= 3 * TOLERANCE, (fault, wrong, sound)  # nan leaves it too


def test_first_loss_is_the_reference_at_a_zero_bias(worker, ids):
    from hypha_tpu.models.routed import STATE
    from perfbench.reference import nemotron_h

    model, variables = worker
    loss, _ = program_loss_and_grads(model, variables, {STATE: variables[STATE]}, ids)
    assert abs(float(loss) - nemotron_h.first_loss(SMALL, ids, SEED)) < TOLERANCE


def test_a_choice_given_from_outside_is_taken_and_a_pass_own_choice_changes_nothing(biased, ids):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import nemotron_h

    w, row = biased[0], jnp.asarray(ids[0])
    with jax.default_matmul_precision("highest"):
        own = nemotron_h.chosen(w, row, SMALL)
        assert sorted(own) == [j for j, kind in enumerate(KINDS) if kind == "experts"]
        free = float(nemotron_h.sequence_nll(w, row, SMALL))
        assert float(nemotron_h.sequence_nll(w, row, SMALL, None, own)) == free
        other = {j: (idx + 1) % nemotron_h.routed(SMALL)[0] for j, idx in own.items()}
        assert abs(float(nemotron_h.sequence_nll(w, row, SMALL, None, other)) - free) > 1e-3
        # the control with the float32 pass's experts: what is left is the products' rounding alone
        low = float(nemotron_h.sequence_nll(w, row, SMALL, "float8_e4m3fn", own))
        assert 1e-3 < abs(low - free) < 0.1 * abs(free)


def test_products_in_float8_leave_the_reference_by_more_than_float32_noise(biased, ids, sound):
    from perfbench.reference import nemotron_h

    low = float(reference_loss(nemotron_h, biased[0], ids, SMALL, operands="float8_e4m3fn"))
    assert abs(low - sound) > 10 * TOLERANCE


CATALOG_ROW = {  # the catalog row's config (architectures.jsonl, Nemotron-Labs-TwoTower-30B-A3B-Base-BF16)
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None], "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}
CELL, CONFIG_NAME = "nemotron-twotower-ctx-d7.steps", "nemotron-twotower-ctx-d7"


def test_the_cells_configuration_is_the_catalog_rows_but_for_what_reduced_names():
    from hypha_tpu.ops.ssd_scan import CHUNK
    from perfbench.reference import nemotron_h

    config = json.loads((REPO / "perfbench" / "configs" / f"{CONFIG_NAME}.json").read_text())
    entry = next(c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"] if c["name"] == CONFIG_NAME)
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert entry["reduced"] == config["reduced"] == list(config["source_values"]) == list(
        config["reduced_why"]) == reduced
    assert entry["source"] == config["source"]
    assert {k: config[k] for k in CATALOG_ROW if k not in reduced} == {
        k: v for k, v in CATALOG_ROW.items() if k not in reduced}
    assert config["source_values"] == {k: CATALOG_ROW[k] for k in reduced}
    assert config["layers_run"] == list(range(7)) and config["num_hidden_layers"] == 7
    assert "".join(config["hybrid_override_pattern"][i] for i in config["layers_run"]) == "MEMEM*E"
    assert nemotron_h.kinds(config) == ["mamba2", "experts", "mamba2", "experts", "mamba2", "full_attention", "experts"]
    assert config["chunk_size"] == CHUNK  # the program's constant is the source's
    # d_inner is heads x head size (4096); the row's expand (2) is not read: 2 x 2688 is another number
    assert config["mamba_num_heads"] * config["mamba_head_dim"] == 4096 != config["expand"] * config["hidden_size"]
    assert "expand" in config["assumed"]["unused_keys"]
    share = config["share"]
    assert share["experts_routed"] == 128 and config["n_routed_experts"] * share["chips_sharing_a_layers_experts"] == 128
    assert config["vocab_size"] * share["chips_sharing_embedding_and_head"] == 131072
    assert set(config["assumed"]) >= {
        "in_projection_order", "no_position_encoding", "selection_bias", "initializers", "precision"}
    assert len(config["stands_for"]) > 100 and "denoiser" in config["not_run"]
    # PR 46, after review: no limit on the first loss against the reference separates bf16 from fp8 in this
    # cell, so the cell holds the band around ln V, and the reference stays for the CPU tests and the probe
    checks = config["checks"]
    assert "reference" not in checks and "reference_tolerance" not in checks
    assert checks["vocabulary"] == config["vocab_size"] and 0 < checks["loss_first_tolerance"] <= 0.1
    assert "perfbench/reference/nemotron_h.py" in checks["reference_not_held"]
    # the job keys say what the file says
    sets = dict(s.removeprefix("job.model_config.").split("=", 1) for s in config["job_sets"][1:])
    assert config["job_sets"][0] == "job.model_family=nemotron_h"
    assert json.loads(sets["layers_run"]) == config["layers_run"]
    assert json.loads(sets["pattern"]) == config["hybrid_override_pattern"]
    for key, source in (
            ("vocab_size", "vocab_size"), ("hidden_size", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"), ("mamba_num_heads", "mamba_num_heads"),
            ("mamba_head_dim", "mamba_head_dim"), ("ssm_state_size", "ssm_state_size"), ("n_groups", "n_groups"),
            ("conv_kernel", "conv_kernel"), ("experts_per_token", "num_experts_per_tok"),
            ("moe_intermediate_size", "moe_intermediate_size"), ("experts_held", "n_routed_experts"),
            ("shared_expert_intermediate_size", "moe_shared_expert_intermediate_size")):
        assert int(sets[key]) == config[source], key
    assert int(sets["num_experts"]) == share["experts_routed"] and int(sets["expert_offset"]) == share["expert_offset"]
    assert float(sets["route_scale"]) == config["routed_scaling_factor"]
    assert float(sets["rms_eps"]) == config["layer_norm_epsilon"]
    assert not any("chunk" in k and k != "moe_chunk" for k in sets)  # no key sets the scan's chunk
    traffic = json.loads((REPO / "perfbench" / "traffic" / f"{CELL}.json").read_text())
    assert traffic["data"]["modulus"] <= config["vocab_size"]
    assert int(sets["max_seq_len"]) == config["max_position_embeddings"] >= traffic["sequence"]
    assert traffic["data"]["sequences"] >= 2 * 3 * traffic["inner_steps"] * traffic["batch"]
    assert traffic["inner_steps"] % 8 == 0
