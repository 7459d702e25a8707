"""The plain reference of the ``keye_vl2`` configurations (Keye-VL-2.0's
language model on text): the layer's forward pass, both losses and their
gradients in ``jax.numpy`` and float32 at ``highest`` matmul precision, on
weights it makes itself from the seed.

Nothing of the program is imported and nothing it made is read: no kernel, no
packed mask, no bisection, no grouped product, no ``hypha_tpu``. The layer (the
items the catalog's config has no key for are the configuration file's
``assumed``):

* ``h = E[ids]``; 4 blocks ``h += attn(RMSNorm(h))``, ``h += moe(RMSNorm(h))``;
  ``logits = RMSNorm(h) W_head``;
* attention: ``q, k, v`` from ``x = RMSNorm(h)`` with no bias; ``q`` and ``k``
  RMS-normed over the head with a learned weight, then rotate-half RoPE at
  ``rope_theta`` over the whole head; query ``t`` attends to the picked set
  ``S_t`` alone: a softmax masked to ``S_t``, one set for all heads;
* the indexer, on ``stop_gradient(x)``: ``qI = W x`` (16 heads of 64), ``kI =
  LayerNorm(W x)`` (one head, weight and bias), RoPE over the 64 of both, ``a =
  W x`` in float32; ``I[t, s] = sum_j a[t, j] * 16^-1/2 * 64^-1/2 * relu(qI[t,
  j] . kI[s])``, a score of -0.0 counted as +0.0; ``S_t`` the positions of the
  ``min(t + 1, topk)`` largest of ``I[t, 0..t]`` by ``jax.lax.top_k`` on the
  whole row (ties to the earlier position);
* the indexer's objective, a layer: ``p[t, s]`` the mean over the 32 heads of
  the masked softmax, under ``stop_gradient``; ``L = mean_t sum_{s in S_t} p
  (log p - log softmax_{S_t}(I)[s])``; the whole objective is the
  cross-entropy plus the layers' sum;
* the routed part: ``p = softmax(m W_r)`` over all the layer's experts, the 8
  largest, their ``p`` divided by their sum (``norm_topk_prob``), and the
  weighted sum of the chosen experts' SwiGLUs; no shared expert, no bias.

**The share.** The configuration holds ``num_experts`` of the layer's
``share.experts_routed`` experts, from ``share.expert_offset`` on: the router
keeps its full width, and the sum over chosen experts runs over the held ones
only, as in the program: a loop over the held experts, each run densely on
every token with a weight that is zero where the token did not choose it.

The losses are the program's: the mean cross-entropy of position t's logits
against token t + 1 over the first S - 1 positions of every row, and the KL as
above. Weights are data: ``table`` says where the worker's flax module keeps
each, in what shape and from which initializer, and ``weights`` replays flax's
key derivation (``tests/perfbench/test_reference_keye_vl2.py`` holds them to
the module's to an ulp). One sequence at a time, the index scores as whole
rows and attention as a masked softmax in blocks of queries, the loss in
blocks of positions, so that 16384 positions fit a chip beside 1.9 GB of weights.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024  # positions a block of logits
QUERY_BLOCK = 512  # queries a block of index scores and attention scores: [32, 512, S] f32


def routed(c: dict) -> tuple[int, int, int]:
    """(experts the router scores, experts held here, the first held)."""
    share = c.get("share", {})
    return (share.get("experts_routed", c["num_experts"]), c["num_experts"],
            share.get("expert_offset", 0))


def table(c: dict) -> dict[str, tuple]:
    """weight -> (path in the worker's ``params`` tree, number among the
    parameters its flax scope draws, shape, initializer). The scope is the
    path without its last element."""
    d, hd, v = c["hidden_size"], c["head_dim"], c["vocab_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    sa = c["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    f = c["moe_intermediate_size"]
    experts, held, _ = routed(c)
    out = {"embed": (("embed_tokens",), 1, (v, d), "normal_1")}
    for i in range(c["num_hidden_layers"]):
        layer, attn, mlp = f"layers_{i}", (f"layers_{i}", "self_attn"), (f"layers_{i}", "mlp")
        out |= {
            f"{i}.input_norm": ((layer, "input_layernorm", "weight"), 1, (d,), "ones"),
            f"{i}.q": ((*attn, "q_proj", "kernel"), 1, (d, q), "lecun_normal"),
            f"{i}.k": ((*attn, "k_proj", "kernel"), 1, (d, kv), "lecun_normal"),
            f"{i}.v": ((*attn, "v_proj", "kernel"), 1, (d, kv), "lecun_normal"),
            f"{i}.q_norm": ((*attn, "q_norm"), 1, (hd,), "ones"),
            f"{i}.k_norm": ((*attn, "k_norm"), 2, (hd,), "ones"),
            f"{i}.index_q": ((*attn, "index_q_proj", "kernel"), 1, (d, j * di), "lecun_normal"),
            f"{i}.index_k": ((*attn, "index_k_proj", "kernel"), 1, (d, di), "lecun_normal"),
            f"{i}.index_k_norm": ((*attn, "index_k_norm", "scale"), 1, (di,), "ones"),
            f"{i}.index_k_bias": ((*attn, "index_k_norm", "bias"), 2, (di,), "zeros"),
            f"{i}.index_w": ((*attn, "index_weights_proj", "kernel"), 1, (d, j), "lecun_normal"),
            f"{i}.o": ((*attn, "o_proj", "kernel"), 1, (q, d), "lecun_normal"),
            f"{i}.post_attn_norm": ((layer, "post_attention_layernorm", "weight"), 1, (d,), "ones"),
            f"{i}.router": ((*mlp, "router"), 1, (d, experts), "lecun_normal"),
            f"{i}.experts_gate": ((*mlp, "experts_gate"), 2, (held, d, f), "lecun_normal_each"),
            f"{i}.experts_up": ((*mlp, "experts_up"), 3, (held, d, f), "lecun_normal_each"),
            f"{i}.experts_down": ((*mlp, "experts_down"), 4, (held, f, d), "lecun_normal_each"),
        }
    out["norm"] = (("norm", "weight"), 1, (d,), "ones")
    out["head"] = (("lm_head",), 2, (v, d), "normal_0.02")
    return out


INDEXER = ("index_q", "index_k", "index_k_norm", "index_k_bias", "index_w")  # the leaves the KL alone trains

INITIALIZERS = {
    "normal_1": jax.nn.initializers.normal(1.0),
    "normal_0.02": jax.nn.initializers.normal(0.02),
    "lecun_normal": jax.nn.initializers.lecun_normal(),
    "lecun_normal_each": jax.nn.initializers.lecun_normal(batch_axis=(0,)),
    "ones": jax.nn.initializers.ones,
    "zeros": jax.nn.initializers.zeros,
}


def _fold(root, scope: tuple, count: int):
    m = hashlib.sha1()
    for x in (*scope, count):
        m.update(x.encode() if isinstance(x, str) else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def weights(config: dict, model_seed: int) -> dict:
    """Every weight, float32, on the device, in one jitted call from the seed."""
    spec = table(config)

    def make(root):
        return {
            name: INITIALIZERS[init](_fold(root, path[:-1], count), shape, jnp.float32)
            for name, (path, count, shape, init) in spec.items()
        }

    return jax.jit(make)(jax.random.key(model_seed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def _rope(x, theta):
    """Rotate-half over the whole head: x [S, heads, head_dim]."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _matmul(operands):
    def low(a):
        return a.astype(operands).astype(jnp.float32) if operands else a

    return low, lambda a, b: low(a) @ low(b)


def index_scores(w: dict, i: int, x, c: dict, low, mm):
    """Layer ``i``'s index scores from the normed input ``x`` [S, d], the input
    detached: a function of the query positions ``qpos`` [rows] that gives
    ``I`` [rows, S]; a zero is +0.0."""
    sa, s = c["sa_config"], x.shape[0]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    x = jax.lax.stop_gradient(x)
    qi = _rope(mm(x, w[f"{i}.index_q"]).reshape(s, j, di), c["rope_theta"])
    ki = _layer_norm(mm(x, w[f"{i}.index_k"]), w[f"{i}.index_k_norm"], w[f"{i}.index_k_bias"], c["rms_norm_eps"])
    ki = _rope(ki[:, None, :], c["rope_theta"])[:, 0]
    a = (x @ w[f"{i}.index_w"]) * (j**-0.5 * di**-0.5)  # float32 whatever the operands

    def rows(qpos):
        z = jnp.einsum("tjd,sd->tjs", low(qi[qpos]), low(ki))
        scores = jnp.sum(a[qpos][:, :, None] * jax.nn.relu(z), axis=1)
        return jnp.where(scores == 0, 0.0, scores)

    return rows


def picked(scores, qpos, topk: int):
    """bool [rows, S]: ``S_t`` of each row's query position by ``lax.top_k``
    on the whole row."""
    s = scores.shape[1]
    causal = jnp.arange(s)[None, :] <= qpos[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, s))
    chosen = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return chosen & causal  # a row with fewer than topk causal keys picked some that are not there


def attention(w: dict, i: int, x, c: dict, low, mm):
    """Layer ``i``'s attention output [S, d] and its indexer objective."""
    s = x.shape[0]
    heads, kv_heads, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta, topk = c["rms_norm_eps"], c["rope_theta"], c["sa_config"]["topk"]
    q = _rope(_rms(mm(x, w[f"{i}.q"]).reshape(s, heads, hd), w[f"{i}.q_norm"], eps), theta)
    k = _rope(_rms(mm(x, w[f"{i}.k"]).reshape(s, kv_heads, hd), w[f"{i}.k_norm"], eps), theta)
    v = mm(x, w[f"{i}.v"]).reshape(s, kv_heads, hd)
    q = low(q).reshape(s, kv_heads, heads // kv_heads, hd)  # heads j*group.. share kv head j
    scores_of = index_scores(w, i, x, c, low, mm)

    def block(qpos):
        scores = scores_of(qpos)
        keep = picked(scores, qpos, topk)
        logits = jnp.einsum("qjgd,kjd->jgqk", q[qpos], low(k)) * hd**-0.5
        p = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        target = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        on = keep & (target > 0)
        kl = jnp.where(on, target * (jnp.log(jnp.where(on, target, 1.0)) - jnp.where(on, logq, 0.0)), 0.0)
        return jnp.einsum("jgqk,kjd->qjgd", low(p), low(v)), jnp.sum(kl)

    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    o, kl = jax.lax.map(block, jnp.arange(s).reshape(-1, size))
    return mm(o.reshape(s, heads * hd), w[f"{i}.o"]), jnp.sum(kl) / s


def route(w: dict, i: int, m, c: dict):
    """A layer's choice: ``idx`` [S, k] and the weights ``wt`` [S, k]."""
    p = jax.nn.softmax(jnp.dot(m, w[f"{i}.router"], precision=jax.lax.Precision.HIGHEST), axis=-1)
    wt, idx = jax.lax.top_k(p, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        wt = wt / wt.sum(-1, keepdims=True)
    return idx, wt


def experts_part(w: dict, i: int, m, c: dict, mm):
    """What the held experts add for the tokens that chose them."""
    _, held, offset = routed(c)
    idx, wt = route(w, i, m, c)
    out = jnp.zeros_like(m)
    for e in range(held):
        mine = jnp.sum(jnp.where(idx == offset + e, wt, 0.0), axis=-1)  # 0 where not chosen
        act = jax.nn.silu(mm(m, w[f"{i}.experts_gate"][e])) * mm(m, w[f"{i}.experts_up"][e])
        out += mine[:, None] * mm(act, w[f"{i}.experts_down"][e])
    return out


def sequence_losses(w: dict, ids, config: dict, operands: str | None = None):
    """For one sequence: the sum over t < S - 1 of -log p(ids[t + 1] |
    ids[..t]), and the indexer's objective summed over the layers.
    ``operands`` is for the control alone: a type below float32 that both
    operands of every matrix product are rounded to, the sums staying float32."""
    c = config
    if c.get("hidden_act", "silu") != "silu" or c.get("tie_word_embeddings") or c.get("mlp_only_layers"):
        raise ValueError("this reference is the silu, untied-head layer with experts in every block")
    low, mm = _matmul(operands)
    eps, s = c["rms_norm_eps"], ids.shape[0]
    h = w["embed"][ids]
    kl = jnp.float32(0.0)
    for i in range(c["num_hidden_layers"]):
        a, layer_kl = attention(w, i, _rms(h, w[f"{i}.input_norm"], eps), c, low, mm)
        h, kl = h + a, kl + layer_kl
        h = h + experts_part(w, i, _rms(h, w[f"{i}.post_attn_norm"], eps), c, mm)
    h = _rms(h, w["norm"], eps)
    total = jnp.float32(0.0)
    for start in range(0, s - 1, LOSS_BLOCK):
        stop = min(start + LOSS_BLOCK, s - 1)
        logits = mm(h[start:stop], w["head"].T)
        got = jnp.take_along_axis(logits, ids[start + 1:stop + 1, None], axis=-1)[:, 0]
        total += jnp.sum(jax.nn.logsumexp(logits, axis=-1) - got)
    return total, kl


def objective(w: dict, input_ids, config: dict):
    """What the step differentiates, and its two parts: the mean
    cross-entropy plus the mean over rows of the layers' KL."""
    rows, s = input_ids.shape
    parts = [sequence_losses(w, row, config) for row in input_ids]
    ce = sum(p[0] for p in parts) / (rows * (s - 1))
    kl = sum(p[1] for p in parts) / rows
    return ce + kl, (ce, kl)


def first_loss(config: dict, input_ids: np.ndarray, model_seed: int,
               operands: str | None = None) -> float:
    """The loss of the worker's first step: seeded weights, its first batch."""
    w = weights(config, model_seed)
    with jax.default_matmul_precision("highest"):
        nll = jax.jit(lambda w, ids: sequence_losses(w, ids, config, operands)[0])
        total = sum(float(nll(w, jnp.asarray(row, jnp.int32))) for row in input_ids)
    rows, s = input_ids.shape
    return total / (rows * (s - 1))
