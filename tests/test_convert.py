"""HF checkpoint conversion: converted native models must reproduce the HF
torch models' logits (the contract that makes ``gpt2`` / Llama-format
repos usable as job model sources)."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from hypha_tpu.models import GPT2, GPT2Config, Llama, LlamaConfig
from hypha_tpu.models.convert import convert_state_dict, load_checkpoint_files

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402


@pytest.mark.slow  # ~22 s torch+HF logit parity — tier-1 wall budget (the
# PR 4 precedent); the conversion path stays covered by the faster
# per-family convert tests below.
def test_gpt2_conversion_matches_hf_logits():
    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2, n_head=2
    )
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, 96, (2, 16))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    cfg = GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2, n_head=2, dtype="float32"
    )
    model = GPT2(cfg)
    template = model.init(jax.random.key(0), ids.astype(np.int32))
    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    params = convert_state_dict("gpt2", state, template)
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_llama_conversion_matches_hf_logits():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        attention_bias=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(1)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(1).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    cfg = LlamaConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        max_seq_len=64,
        rms_eps=1e-5,
        dtype="float32",
    )
    model = Llama(cfg)
    template = model.init(jax.random.key(0), ids.astype(np.int32))
    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    params = convert_state_dict("llama", state, template)
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_unmapped_tensor_fails_loudly():
    with pytest.raises(KeyError, match="unmapped"):
        convert_state_dict(
            "gpt2", {"h.0.attn.c_weird.weight": np.zeros((2, 2))}, {"params": {}}
        )
    with pytest.raises(ValueError, match="no HF converter"):
        convert_state_dict("resnet", {}, {})


def test_missing_tensor_fails_loudly():
    cfg = GPT2Config(
        vocab_size=16, n_positions=8, n_embd=8, n_layer=1, n_head=2, dtype="float32"
    )
    model = GPT2(cfg)
    template = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    with pytest.raises(KeyError):
        convert_state_dict("gpt2", {"wte.weight": np.zeros((16, 8), np.float32)}, template)


def test_load_checkpoint_files_formats(tmp_path):
    from safetensors.numpy import save_file

    save_file({"a": np.ones(2, np.float32)}, str(tmp_path / "x.safetensors"))
    torch.save({"b": torch.ones(3)}, tmp_path / "y.bin")
    state = load_checkpoint_files(
        [tmp_path / "x.safetensors", tmp_path / "y.bin", tmp_path / "z.json"]
    )
    assert set(state) == {"a", "b"}
    assert state["b"].shape == (3,)


def test_mistral_conversion_matches_hf_logits():
    """Mistral is Llama-architecture with a sliding window; its torch
    checkpoints load into the native Llama module with logit parity
    (VERDICT r2 missing #2 — torch-only modern decoders)."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        sliding_window=None,
        tie_word_embeddings=False,
    )
    torch.manual_seed(2)
    hf = transformers.MistralForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(2).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    cfg = LlamaConfig.from_hf(hf_cfg.to_dict(), dtype="float32")
    model = Llama(cfg)
    template = model.init(jax.random.key(0), ids.astype(np.int32))
    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    params = convert_state_dict("mistral", state, template)
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_qwen2_conversion_matches_hf_logits_with_biases_and_tied_head():
    """Qwen2 adds q/k/v biases and (small sizes) tied embeddings; both map
    into the native Llama module."""
    hf_cfg = transformers.Qwen2Config(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=True,
        use_sliding_window=False,
    )
    torch.manual_seed(3)
    hf = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(3).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    cfg = LlamaConfig.from_hf(hf_cfg.to_dict(), dtype="float32")
    assert cfg.attn_bias and cfg.tie_word_embeddings
    model = Llama(cfg)
    template = model.init(jax.random.key(0), ids.astype(np.int32))
    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    params = convert_state_dict("qwen2", state, template)
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_mistral_sliding_window_masks_long_range():
    """With sliding_window set and S > window, positions must not attend
    past the window (the Mistral local-attention contract)."""
    cfg = LlamaConfig(
        vocab_size=32, hidden_size=16, intermediate_size=32,
        num_layers=1, num_heads=2, num_kv_heads=2, max_seq_len=32,
        dtype="float32", sliding_window=4,
    )
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 32, (1, 16)).astype(np.int32)
    params = model.init(jax.random.key(0), ids)
    base = np.asarray(model.apply(params, ids))
    # Perturb token 0: logits at positions >= window must be unaffected
    # (outside every window), positions < window change.
    ids2 = ids.copy(); ids2[0, 0] = (ids2[0, 0] + 1) % 32
    pert = np.asarray(model.apply(params, ids2))
    assert not np.allclose(base[0, 1:4], pert[0, 1:4])
    np.testing.assert_allclose(base[0, 4:], pert[0, 4:], rtol=1e-5, atol=1e-5)


def test_registry_builds_mistral_and_qwen2_families():
    from hypha_tpu.models.registry import build_model

    m, cfg = build_model({
        "family": "mistral",
        "hf_config": {"model_type": "mistral", "vocab_size": 64,
                      "hidden_size": 16, "intermediate_size": 32,
                      "num_hidden_layers": 1, "num_attention_heads": 2,
                      "num_key_value_heads": 1, "sliding_window": 8},
    })
    assert isinstance(m, Llama) and cfg.sliding_window == 8
    m2, cfg2 = build_model({"family": "qwen2", "config": {
        "vocab_size": 64, "hidden_size": 16, "intermediate_size": 32,
        "num_layers": 1, "num_heads": 2, "num_kv_heads": 1}})
    assert isinstance(m2, Llama) and cfg2.attn_bias


def test_training_loop_loss_parity_vs_torch():
    """Short end-to-end parity: identical weights + data + AdamW, our jitted
    step vs the reference-style torch loop — loss trajectories must agree
    (BASELINE metric: 'eval-loss parity vs CUDA/accelerate path')."""
    from harness.eval_parity import jax_losses, torch_losses

    torch.manual_seed(0)
    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=1, n_head=2,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    hf = transformers.GPT2LMHeadModel(hf_cfg)
    ids = np.random.default_rng(1).integers(0, 96, (2, 32)).astype(np.int64)
    state = {k: v.numpy().copy() for k, v in hf.state_dict().items()}
    lt = torch_losses(hf, ids, 8)
    lj = jax_losses(hf, state, ids.astype(np.int32), 8)
    assert max(abs(a - b) for a, b in zip(lt, lj)) < 1e-3


def test_gemma_conversion_matches_hf_logits():
    """Gemma: offset-RMSNorm (1+w), GeGLU, sqrt(E) embedding scale, explicit
    head_dim, tied head — all map into the native Llama module with logit
    parity against the torch reference."""
    hf_cfg = transformers.GemmaConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,  # != hidden/heads: exercises the override
        max_position_embeddings=64,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        hidden_activation="gelu_pytorch_tanh",
    )
    torch.manual_seed(5)
    hf = transformers.GemmaForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(5).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    from hypha_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.from_hf(hf_cfg.to_dict(), dtype="float32")
    assert cfg.rms_offset and cfg.embed_scale and cfg.mlp_act == "gelu_tanh"
    assert cfg.head_dim == 16 and cfg.tie_word_embeddings
    model = Llama(cfg)
    template = model.init(jax.random.key(0), ids.astype(np.int32))
    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    params = convert_state_dict("gemma", state, template)
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def _tiny_llama(seed: int = 7):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        attention_bias=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(seed)
    return transformers.LlamaForCausalLM(hf_cfg).eval(), hf_cfg


def _native_template(hf_cfg, ids):
    cfg = LlamaConfig.from_hf(hf_cfg.to_dict(), dtype="float32")
    model = Llama(cfg)
    return model, model.init(jax.random.key(0), ids.astype(np.int32))


def test_sharded_checkpoint_conversion_matches_hf_logits(tmp_path):
    """The real HF sharded layout (model.safetensors.index.json written by
    save_pretrained, the format every released >2 GB checkpoint uses) must
    stream-convert with logit parity (VERDICT r3 missing #1)."""
    from hypha_tpu.models.convert import ShardedCheckpoint, convert_checkpoint

    hf, hf_cfg = _tiny_llama()
    # Force sharding: the tiny model is ~200 KB, so a 50 KB cap produces a
    # multi-file repo with a real index.json.
    hf.save_pretrained(tmp_path, max_shard_size="50KB", safe_serialization=True)
    assert (tmp_path / "model.safetensors.index.json").exists()
    assert len(list(tmp_path.glob("model-*.safetensors"))) > 1

    ids = np.random.default_rng(7).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    model, template = _native_template(hf_cfg, ids)
    # Tensor names must be discoverable across shards.
    with ShardedCheckpoint(tmp_path) as ckpt:
        assert "model.embed_tokens.weight" in ckpt.keys()
    params = convert_checkpoint("llama", tmp_path, template)
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_sharded_checkpoint_bf16_and_put_streaming(tmp_path):
    """bf16 shards (how Llama-2 actually ships) read through the native
    BF16 mmap path; the ``put`` callback sees every leaf exactly once so
    conversion can stream to device without a host-side full tree."""
    from hypha_tpu.models.convert import convert_checkpoint

    hf, hf_cfg = _tiny_llama(8)
    hf.to(torch.bfloat16).save_pretrained(
        tmp_path, max_shard_size="50KB", safe_serialization=True
    )
    ids = np.random.default_rng(8).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf.float()(torch.from_numpy(ids)).logits.numpy()

    model, template = _native_template(hf_cfg, ids)
    seen: list[str] = []

    def put(name, arr):
        seen.append(name)
        assert arr.dtype == np.float32 and arr.flags["C_CONTIGUOUS"]
        return jax.device_put(arr)

    params = convert_checkpoint("llama", tmp_path, template, put=put)
    n_leaves = len(jax.tree_util.tree_leaves(template))
    assert len(seen) == len(set(seen)) == n_leaves
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    # bf16 storage costs ~3 decimal digits of mantissa.
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_sharded_checkpoint_dir_without_index(tmp_path):
    """A directory holding a single model.safetensors (small-repo layout)
    resolves without an index file."""
    from hypha_tpu.models.convert import convert_checkpoint

    hf, hf_cfg = _tiny_llama(9)
    hf.save_pretrained(tmp_path, safe_serialization=True)
    assert not (tmp_path / "model.safetensors.index.json").exists()
    ids = np.random.default_rng(9).integers(0, 96, (1, 8))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()
    model, template = _native_template(hf_cfg, ids)
    import ml_dtypes

    params = convert_checkpoint(
        "llama", tmp_path, template, dtype=ml_dtypes.bfloat16
    )
    leaf = jax.tree_util.tree_leaves(params)[0]
    assert leaf.dtype == ml_dtypes.bfloat16
    got = np.asarray(
        model.apply(jax.tree.map(lambda x: x.astype(np.float32), params),
                    ids.astype(np.int32))
    )
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_mixtral_conversion_matches_hf_logits(tmp_path):
    """HF Mixtral stores experts as separate w1/w2/w3 Linears; the
    converter stacks them into the native [E, ...] tensors (single
    batched MXU matmuls) with logit parity. Dropless routing makes the
    comparison exact (no capacity drops)."""
    import dataclasses

    from hypha_tpu.models import Mixtral, MixtralConfig
    from hypha_tpu.models.convert import convert_checkpoint

    hf_cfg = transformers.MixtralConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        router_aux_loss_coef=0.0,
    )
    torch.manual_seed(13)
    hf = transformers.MixtralForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(13).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    cfg = MixtralConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2,
        num_experts=4, experts_per_token=2, max_seq_len=64,
        rope_theta=10000.0, rms_eps=1e-5, dtype="float32",
    )
    model = Mixtral(cfg, dropless=True)
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), ids.astype(np.int32))
    )

    # both the in-memory and the streaming/sharded paths must stack
    from hypha_tpu.models.convert import convert_state_dict

    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    params = convert_state_dict("mixtral", state, template)
    got, _aux = model.apply(params, ids.astype(np.int32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-4, atol=3e-4)

    hf.save_pretrained(tmp_path, max_shard_size="50KB", safe_serialization=True)
    assert (tmp_path / "model.safetensors.index.json").exists()
    params2 = convert_checkpoint("mixtral", tmp_path, template)
    got2, _ = model.apply(params2, ids.astype(np.int32))
    np.testing.assert_allclose(np.asarray(got2), want, rtol=3e-4, atol=3e-4)


def test_qwen3_conversion_matches_hf_logits_qk_norm():
    """Qwen3 replaces qwen2's projection biases with per-head QK-norm
    (q_norm/k_norm RMS weights before RoPE) and pins an explicit head_dim;
    both map into the native Llama module via the qwen3 family."""
    hf_cfg = transformers.Qwen3Config(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        max_position_embeddings=64,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        use_sliding_window=False,
        attention_bias=False,
    )
    torch.manual_seed(5)
    hf = transformers.Qwen3ForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(5).integers(0, 96, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids)).logits.numpy()

    cfg = LlamaConfig.from_hf(hf_cfg.to_dict(), dtype="float32")
    assert cfg.qk_norm and not cfg.attn_bias and cfg.head_dim == 8
    model = Llama(cfg)
    template = model.init(jax.random.key(0), ids.astype(np.int32))
    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    params = convert_state_dict("qwen3", state, template)
    got = np.asarray(model.apply(params, ids.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_qwen3_tied_checkpoint_materializes_head():
    """Real small Qwen3 repos tie embeddings and their on-disk safetensors
    drop the duplicate lm_head tensor; conversion into an untied template
    must materialize the head from embed_tokens (the qwen2/gemma path)."""
    hf_cfg = transformers.Qwen3Config(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=True, use_sliding_window=False,
        attention_bias=False,
    )
    torch.manual_seed(9)
    hf = transformers.Qwen3ForCausalLM(hf_cfg).eval()
    state = {k: v.numpy() for k, v in hf.state_dict().items()}
    state.pop("lm_head.weight", None)  # what safetensors actually ships

    import dataclasses

    cfg = dataclasses.replace(
        LlamaConfig.from_hf(hf_cfg.to_dict(), dtype="float32"),
        tie_word_embeddings=False,  # untied template: head must materialize
    )
    ids = np.random.default_rng(9).integers(0, 96, (1, 8)).astype(np.int32)
    model = Llama(cfg)
    template = model.init(jax.random.key(0), ids)
    params = convert_state_dict("qwen3", state, template)
    got = np.asarray(model.apply(params, ids))
    with torch.no_grad():
        want = hf(torch.from_numpy(ids.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
