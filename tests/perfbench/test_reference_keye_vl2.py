"""The plain reference of the keye_vl2 configurations
(``perfbench/reference/keye_vl2.py``) against the program at a small size on
the CPU in float32, with one rank's share of the experts and norm weights and a
key-norm bias that are not their initial ones and zeros: the seeded weights to an
ulp, the cross-entropy, the indexer's KL and the gradient of every leaf;
the exact zeros the two ``stop_gradient``s give; and each of thirteen faults
planted in a copy of the reference leaves the program."""

from __future__ import annotations

import json
import sys
import types

import numpy as np
import pytest

from perfbench_helpers import REPO

SA = {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
      "kv_chunk_size": 32, "q_chunk_size": 32, "topk": 16}
SMALL = dict(  # the reference's keys (the source's names) ...
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=10000000, rms_norm_eps=1e-6, sa_config=SA, num_experts=4,
    share={"experts_routed": 16, "expert_offset": 8}, num_experts_per_tok=3, moe_intermediate_size=32,
    norm_topk_prob=True, vocab_size=256, hidden_act="silu", tie_word_embeddings=False, mlp_only_layers=[],
)
PROGRAM = dict(  # ... and the same stack in the program's
    vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    index_heads=4, index_head_dim=8, index_topk=16, q_chunk=32, kv_chunk=32, num_experts=16,
    experts_held=4, expert_offset=8, experts_per_token=3, moe_intermediate_size=32, dtype="float32",
    moe_chunk=64,
)
SEED = 2147485132 % 2**31
TOLERANCE = 2e-5  # float32 against float32: the sound difference is 1e-6 here


def program(ids):
    """The ``keye_vl2`` family module as the worker builds and seeds it."""
    from hypha_tpu.executor import training

    spec = {"family": "keye_vl2", "config": PROGRAM, "seed": SEED, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    model, variables, causal_lm, _ = training._init_model(
        cfg, None, "/nonexistent", {"input_ids": ids})
    assert causal_lm and sorted(variables) == ["moe_state", "params"]
    return model, variables


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(0, 256, (2, 96)).astype(np.int32)  # six times topk: most queries cut


@pytest.fixture(scope="module")
def worker(ids):
    return program(ids)


def at(tree, path):
    for part in path:
        tree = tree[part]
    return tree


@pytest.fixture(scope="module")
def moved(worker):
    """The reference's weights with every vector (norm weights, the key
    norm's bias) moved off its initial ones and zeros, and the program's
    parameters given the same: a dropped bias or norm is then seen."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import keye_vl2

    w = dict(keye_vl2.weights(SMALL, SEED))
    params = jax.tree.map(lambda x: x, worker[1]["params"])
    rng = np.random.default_rng(17)
    for name, (path, _count, shape, _init) in keye_vl2.table(SMALL).items():
        if len(shape) == 1:
            w[name] = w[name] + jnp.asarray(rng.normal(0, 0.2, shape), jnp.float32)
            at(params, path[:-1])[path[-1]] = w[name]
    return w, params


def program_losses_and_grads(model, params, extras, ids):
    """(cross-entropy, KL), and the gradient of each by every leaf."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import chunked_causal_ce

    body = model.clone(with_head=False)

    def both(params):
        hidden, stats = body.apply({"params": params, **extras}, jnp.asarray(ids))
        ce = chunked_causal_ce(hidden[:, :-1], params[model.head_leaf], jnp.asarray(ids)[:, 1:], chunk=32)
        return jnp.stack([ce, stats["aux_loss"].sum()])

    with jax.default_matmul_precision("highest"):  # a gradient a loss: the grouped product has no batching rule
        grads = [jax.grad(lambda p, part=part: both(p)[part])(params) for part in (0, 1)]
        return both(params), jax.tree.map(lambda *g: jnp.stack(g), *grads)


def reference_losses(module, w, ids, config, operands=None):
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        parts = [module.sequence_losses(w, jnp.asarray(row), config, operands) for row in ids]
        ce = sum(p[0] for p in parts) / (ids.shape[0] * (ids.shape[1] - 1))
        return jnp.stack([ce, sum(p[1] for p in parts) / ids.shape[0]])


def test_the_reference_makes_the_workers_seeded_weights_without_the_worker(worker):
    from perfbench.reference import keye_vl2

    params = worker[1]["params"]
    w = keye_vl2.weights(SMALL, SEED)
    leaves = sum(1 for _ in __import__("jax").tree.leaves(params))
    assert leaves == len(w) == 2 * 17 + 3
    for name, (path, *_rest) in keye_vl2.table(SMALL).items():
        assert at(params, path).shape == w[name].shape, name
        np.testing.assert_allclose(w[name], at(params, path), rtol=5e-7, atol=1e-8, err_msg=name)  # an ulp or two: made under jit here


def test_the_reference_imports_nothing_of_the_program():
    src = (REPO / "perfbench" / "reference" / "keye_vl2.py").read_text()
    code = [x for x in src.split('"""', 2)[2].splitlines() if x.lstrip().startswith(("import ", "from "))]
    assert code and not any("hypha" in x or "flax" in x or "perfbench" in x for x in code), code
    body = src.split('"""', 2)[2]
    assert "lax.top_k" in body and "approx" not in body and "pack" not in body


@pytest.fixture(scope="module")
def sound(worker, moved, ids):
    from hypha_tpu.models.routed import STATE

    model, variables = worker
    return program_losses_and_grads(model, moved[1], {STATE: variables[STATE]}, ids)


def test_both_losses_and_every_leafs_gradient_are_the_programs(sound, moved, ids):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import keye_vl2

    losses, grads = sound
    ref = reference_losses(keye_vl2, moved[0], ids, SMALL)
    ref_grads = jax.jacrev(lambda w: reference_losses(keye_vl2, w, ids, SMALL))(moved[0])
    assert float(jnp.abs(losses - ref).max()) < TOLERANCE, (losses, ref)
    assert 5.0 < float(ref[0]) < 6.5 and 0.01 < float(ref[1]) < 2.0  # ln 256 and the head's spread; a KL
    for name, (path, *_rest) in keye_vl2.table(SMALL).items():
        mine, theirs = at(grads, path), ref_grads[name]
        indexer = name.split(".")[-1] in keye_vl2.INDEXER
        # by the two stop_gradients: the indexer learns from the KL alone, the rest from the cross-entropy alone
        for part, zero in ((0, indexer), (1, not indexer)):
            scale = float(jnp.abs(theirs[part]).max())
            assert (scale == 0.0) == zero and (float(jnp.abs(mine[part]).max()) == 0.0) == zero, (name, part)
            off = float(jnp.abs(mine[part] - theirs[part]).max())
            assert off < 2e-3 * scale + 1e-7, (name, part, off, scale)


def test_the_objective_is_the_cross_entropy_plus_the_layers_kl(moved, ids):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import keye_vl2

    with jax.default_matmul_precision("highest"):
        total, (ce, kl) = keye_vl2.objective(moved[0], jnp.asarray(ids), SMALL)
    parts = reference_losses(keye_vl2, moved[0], ids, SMALL)
    assert float(total) == float(ce + kl) and abs(float(ce) - float(parts[0])) < 1e-6 and abs(
        float(kl) - float(parts[1])) < 1e-6


# fault -> (the reference's own line, the line a copy is given instead)
FAULTS = {
    "the_relu_dropped": ("a[qpos][:, :, None] * jax.nn.relu(z)", "a[qpos][:, :, None] * z"),
    "the_head_weights_dropped": ("a[qpos][:, :, None] * jax.nn.relu(z)", "(j**-0.5 * di**-0.5) * jax.nn.relu(z)"),
    "the_choice_made_over_later_keys_too": (
        "_, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, s))",
        "_, idx = jax.lax.top_k(scores, min(topk, s))"),
    "the_first_topk_queries_cut": ("min(topk, s))", "min(topk, s) // 2)"),
    "the_kl_taken_over_all_causal_keys": (
        "logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)",
        "logq = jax.nn.log_softmax(jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], scores, -jnp.inf), axis=-1)"),
    "the_key_norms_bias_dropped": (
        'w[f"{i}.index_k_norm"], w[f"{i}.index_k_bias"], c["rms_norm_eps"])', 'w[f"{i}.index_k_norm"], 0.0, c["rms_norm_eps"])'),
    "one_heads_probabilities_as_the_target": ("jnp.mean(p, axis=(0, 1))", "p[0, 0]"),
    "sigmoid_scores_in_the_router": (
        'p = jax.nn.softmax(jnp.dot(m, w[f"{i}.router"], precision=jax.lax.Precision.HIGHEST), axis=-1)',
        'p = jax.nn.sigmoid(jnp.dot(m, w[f"{i}.router"], precision=jax.lax.Precision.HIGHEST))'),
    "norm_topk_prob_dropped": ('if c["norm_topk_prob"]:', "if False:"),
    "the_qk_norm_dropped": (
        '    q = _rope(_rms(mm(x, w[f"{i}.q"]).reshape(s, heads, hd), w[f"{i}.q_norm"], eps), theta)\n'
        '    k = _rope(_rms(mm(x, w[f"{i}.k"]).reshape(s, kv_heads, hd), w[f"{i}.k_norm"], eps), theta)\n',
        '    q = _rope(mm(x, w[f"{i}.q"]).reshape(s, heads, hd), theta)\n'
        '    k = _rope(mm(x, w[f"{i}.k"]).reshape(s, kv_heads, hd), theta)\n'),
    "theta_ten_thousand": ('eps, theta, topk = c["rms_norm_eps"], c["rope_theta"],', 'eps, theta, topk = c["rms_norm_eps"], 1e4,'),
    # these two leave both losses as they are and are seen in the gradients' zeros
    "the_indexers_input_not_detached": ("    x = jax.lax.stop_gradient(x)\n", ""),
    "the_target_not_detached": (
        "target = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))", "target = jnp.mean(p, axis=(0, 1))"),
}
IN_THE_GRADIENT = {"the_indexers_input_not_detached", "the_target_not_detached"}


def a_copy_with(fault: str):
    """The reference's source with one line made wrong, as a module of its own."""
    src = (REPO / "perfbench" / "reference" / "keye_vl2.py").read_text()
    right, wrong = FAULTS[fault]
    assert src.count(right) == 1, f"the reference no longer has the line {right!r}"
    module = types.ModuleType(f"keye_vl2_with_{fault}")
    sys.modules[module.__name__] = module  # dataclasses and jit look a module up by name
    exec(compile(src.replace(right, wrong), module.__name__, "exec"), module.__dict__)
    return module


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - IN_THE_GRADIENT))
def test_a_fault_planted_in_a_copy_leaves_the_program_by_more_than_the_tolerance(fault, moved, ids, sound):
    wrong = np.asarray(reference_losses(a_copy_with(fault), moved[0], ids, SMALL))
    assert not np.abs(wrong - np.asarray(sound[0])).max() <= 3 * TOLERANCE, (fault, wrong, sound[0])  # nan leaves it too


@pytest.mark.parametrize("fault", sorted(IN_THE_GRADIENT))
def test_a_dropped_stop_gradient_breaks_the_exact_zeros(fault, moved, ids, sound):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import keye_vl2

    module = a_copy_with(fault)
    wrong = np.asarray(reference_losses(module, moved[0], ids, SMALL))
    assert np.abs(wrong - np.asarray(sound[0])).max() < TOLERANCE  # the losses do not see it
    kl_grads = jax.grad(lambda w: reference_losses(module, w, ids, SMALL)[1])(moved[0])
    leaked = [name for name in kl_grads if name.split(".")[-1] not in keye_vl2.INDEXER
              and float(jnp.abs(kl_grads[name]).max()) > 0.0]
    assert leaked, fault  # the KL now trains leaves of the language model


def test_first_loss_is_the_references_cross_entropy(worker, ids):
    from hypha_tpu.models.routed import STATE
    from perfbench.reference import keye_vl2

    model, variables = worker
    losses, _ = program_losses_and_grads(model, variables["params"], {STATE: variables[STATE]}, ids)
    assert abs(float(losses[0]) - keye_vl2.first_loss(SMALL, ids, SEED)) < TOLERANCE


def test_products_in_float8_leave_the_reference_by_more_than_float32_noise(moved, ids, sound):
    from perfbench.reference import keye_vl2

    low = np.asarray(reference_losses(keye_vl2, moved[0], ids, SMALL, operands="float8_e4m3fn"))
    assert abs(low[0] - float(sound[0][0])) > 10 * TOLERANCE


def test_the_eight_shares_routed_sums_add_up_to_the_uncut_references(moved):
    """The share tied to the model: the routed parts that the program's eight
    ranks compute (2 of 16 experts each), added, are the reference's routed
    part with every expert held."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from hypha_tpu.models.keye_vl2 import KeyeVL2Config
    from hypha_tpu.models.routed import STATE, _MoE
    from perfbench.reference import keye_vl2

    whole = {**SMALL, "num_experts": 16, "share": {"experts_routed": 16, "expert_offset": 0}}
    w = keye_vl2.weights(whole, SEED)
    m = jnp.asarray(np.random.default_rng(5).normal(0, 1, (1, 40, 64)), jnp.float32)
    cfg = KeyeVL2Config(**{**PROGRAM, "experts_held": 2, "expert_offset": 0})
    with jax.default_matmul_precision("highest"):
        uncut = keye_vl2.experts_part(w, 0, m[0], whole, keye_vl2._matmul(None)[1])
        total, pairs = jnp.zeros_like(m), 0
        for rank in range(8):
            share = dataclasses.replace(cfg, expert_offset=2 * rank)
            mine = {"router": w["0.router"], **{
                f"experts_{n}": w[f"0.experts_{n}"][2 * rank:2 * rank + 2] for n in ("gate", "up", "down")}}
            out, stats = _MoE(share).apply({"params": mine, STATE: {"expert_bias": jnp.zeros((16,))}}, m)
            total, pairs = total + out, pairs + int(stats["pairs_computed"])
    assert pairs == m.shape[1] * 3  # every choice of every token is computed on exactly one rank
    np.testing.assert_allclose(total[0], uncut, atol=2e-5)


CATALOG_ROW = {  # the catalog row's config (architectures.jsonl, Keye-VL-2.0-30B-A3B)
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
CELL, CONFIG_NAME = "keye-vl-2-30b-a3b-txt-d4.steps", "keye-vl-2-30b-a3b-txt-d4"


def test_the_cells_configuration_is_the_catalog_rows_but_for_what_reduced_names():
    config = json.loads((REPO / "perfbench" / "configs" / f"{CONFIG_NAME}.json").read_text())
    listed = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in listed["configs"] if c["name"] == CONFIG_NAME)
    reduced = ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    assert entry["reduced"] == config["reduced"] == list(config["source_values"]) == list(
        config["reduced_why"]) == reduced
    assert entry["source"] == config["source"] and entry["source"].startswith("https://huggingface.co/Kwai-Keye/")
    for key, value in CATALOG_ROW.items():
        if key in reduced:
            assert config["source_values"][key] == value and config[key] != value, key
        else:
            assert config[key] == value, key
    for key in ("assumed", "stands_for", "not_run", "layers_run", "share", "flops", "kernels", "checks"):
        assert config.get(key), key
    assert config["share"]["experts_routed"] == 128
    # the cell's first loss is held to this file's reference (PERF.md 6, PR 50)
    checks = config["checks"]
    assert checks["reference"] == "keye_vl2" and 0 < checks["reference_tolerance"] <= 0.003 and checks["reference_reason"]


def test_the_job_the_cell_runs_is_the_configuration_the_reference_reads():
    """``job_sets`` (the program's names) and the source's keys (the
    reference's) say the same model."""
    from hypha_tpu.models.keye_vl2 import KeyeVL2Config

    config = json.loads((REPO / "perfbench" / "configs" / f"{CONFIG_NAME}.json").read_text())
    assert config["job_sets"][0] == "job.model_family=keye_vl2"
    sets = dict(s.removeprefix("job.model_config.").split("=", 1) for s in config["job_sets"][1:])
    cfg = KeyeVL2Config(**{k: json.loads(v) for k, v in sets.items()})
    sa = config["sa_config"]
    assert (cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        config["vocab_size"], config["hidden_size"], config["num_hidden_layers"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"])
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk, cfg.q_chunk, cfg.kv_chunk) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"], sa["q_chunk_size"], sa["kv_chunk_size"])
    assert (cfg.num_experts, cfg.held, cfg.expert_offset, cfg.experts_per_token, cfg.moe_intermediate_size) == (
        config["share"]["experts_routed"], config["num_experts"], config["share"]["expert_offset"],
        config["num_experts_per_tok"], config["moe_intermediate_size"])
    assert (cfg.rope_theta, cfg.rms_eps, cfg.route_norm, cfg.router) == (
        config["rope_theta"], config["rms_norm_eps"], config["norm_topk_prob"], "softmax")
    assert len(config["layers_run"]) == cfg.num_layers and cfg.max_seq_len == config["max_position_embeddings"]
