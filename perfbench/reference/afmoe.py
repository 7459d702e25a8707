"""The plain reference of the ``afmoe`` configurations (Arcee Trinity): the
published layer's forward pass and loss in ``jax.numpy`` and float32 at
``highest`` matmul precision, on weights it makes itself from the seed.

Nothing of the program is imported and nothing it made is read: no kernel,
no grouped product, no ``hypha_tpu``. The layer is the one of the source's
``modeling_afmoe.py`` (the items the catalog's config has no key for are the
configuration file's ``assumed``):

* ``h = E[ids] * sqrt(hidden_size)`` (``mup_enabled``);
* attention: ``q, k, v, g`` projected from ``RMSNorm(h)``; ``q`` and ``k``
  RMS-normed over the head with a learned weight; a ``sliding_attention``
  layer applies rotate-half RoPE and lets query i see keys in
  (i - ``sliding_window``, i], a ``full_attention`` layer applies no position
  encoding and the causal mask; ``o = softmax(q k^T / sqrt(head_dim)) v``,
  ``o = o * sigmoid(g)``, then the output projection;
* ``h += RMSNorm(attn)``; ``m = RMSNorm(h)``; ``h += RMSNorm(f(m))``;
* ``f``: a SwiGLU of ``intermediate_size`` in the leading dense layers; after
  them ``s = sigmoid(m W_r)`` over all the layer's experts, ``idx = top_k(s +
  b)`` with ``b`` the selection bias (zero at the first step), ``w = s[idx]``
  normalised (``route_norm``) and scaled (``route_scale``), and ``f =
  shared(m) + sum_i w_i expert_idx_i(m)``;
* ``logits = RMSNorm(h) W_head``.

**The share.** The configuration holds ``num_experts`` of the layer's
``share.experts_routed`` experts, from ``share.expert_offset`` on: the router
keeps its full width, and the sum over chosen experts runs over the held
ones only, as in the program. ``layers_run`` names the source layers that are
run; ``layer_types`` (the source's, whole) gives each its kind. The routed
part is a loop over the held experts, each run densely on every token with a
weight that is zero where the token did not choose it: 16 times the routed
work, and plain.

The loss is the program's: the mean cross-entropy of position t's logits
against token t + 1 over the first S - 1 positions of every row. Weights are
data: ``TABLE`` says where the worker's flax module keeps each, in what shape
and from which initializer, and ``weights`` replays flax's key derivation
(``tests/perfbench/test_reference_afmoe.py`` holds them to the module's bit
for bit). One sequence at a time, attention in blocks of queries and the loss
in blocks of positions, so that 8192 positions fit a chip beside 2 GB of
weights.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024  # positions a block of logits
QUERY_BLOCK = 1024  # queries a block of attention scores: [kv, group, 1024, S] f32


def routed(c: dict) -> tuple[int, int, int]:
    """(experts the router scores, experts held here, the first held)."""
    share = c.get("share", {})
    return (share.get("experts_routed", c["num_experts"]), c["num_experts"],
            share.get("expert_offset", 0))


def kinds(c: dict) -> list[str]:
    """The attention kind of each layer that is run."""
    run = c.get("layers_run", range(c["num_hidden_layers"]))
    return [c["layer_types"][source] for source in run]


def table(c: dict) -> dict[str, tuple]:
    """weight -> (path in the worker's ``params`` tree, number among the
    parameters its flax scope draws, shape, initializer). The scope is the
    path without its last element."""
    d, hd, v = c["hidden_size"], c["head_dim"], c["vocab_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    f = c["moe_intermediate_size"]
    experts, held, _ = routed(c)
    out = {"embed": (("embed_tokens",), 1, (v, d), "normal_0.02")}
    for i in range(c["num_hidden_layers"]):
        layer, attn, mlp = f"layers_{i}", (f"layers_{i}", "self_attn"), (f"layers_{i}", "mlp")
        out |= {
            f"{i}.input_norm": ((layer, "input_layernorm", "weight"), 1, (d,), "ones"),
            f"{i}.q": ((*attn, "q_proj", "kernel"), 1, (d, q), "lecun_normal"),
            f"{i}.k": ((*attn, "k_proj", "kernel"), 1, (d, kv), "lecun_normal"),
            f"{i}.v": ((*attn, "v_proj", "kernel"), 1, (d, kv), "lecun_normal"),
            f"{i}.g": ((*attn, "gate_proj", "kernel"), 1, (d, q), "lecun_normal"),
            f"{i}.q_norm": ((*attn, "q_norm"), 1, (hd,), "ones"),
            f"{i}.k_norm": ((*attn, "k_norm"), 2, (hd,), "ones"),
            f"{i}.o": ((*attn, "o_proj", "kernel"), 1, (q, d), "lecun_normal"),
            f"{i}.post_attn_norm": ((layer, "post_attention_layernorm", "weight"), 1, (d,), "ones"),
            f"{i}.pre_mlp_norm": ((layer, "pre_mlp_layernorm", "weight"), 1, (d,), "ones"),
            f"{i}.post_mlp_norm": ((layer, "post_mlp_layernorm", "weight"), 1, (d,), "ones"),
        }
        if i < c["num_dense_layers"]:
            wide, inner = c["intermediate_size"], mlp
        else:
            wide, inner = f * c["num_shared_experts"], (*mlp, "shared_experts")
            out |= {
                f"{i}.router": ((*mlp, "router"), 1, (d, experts), "lecun_normal"),
                f"{i}.experts_gate": ((*mlp, "experts_gate"), 2, (held, d, f), "lecun_normal_each"),
                f"{i}.experts_up": ((*mlp, "experts_up"), 3, (held, d, f), "lecun_normal_each"),
                f"{i}.experts_down": ((*mlp, "experts_down"), 4, (held, f, d), "lecun_normal_each"),
            }
        out |= {
            f"{i}.gate": ((*inner, "gate_proj", "kernel"), 1, (d, wide), "lecun_normal"),
            f"{i}.up": ((*inner, "up_proj", "kernel"), 1, (d, wide), "lecun_normal"),
            f"{i}.down": ((*inner, "down_proj", "kernel"), 1, (wide, d), "lecun_normal"),
        }
    out["norm"] = (("norm", "weight"), 1, (d,), "ones")
    out["head"] = (("lm_head",), 2, (v, d), "normal_0.02")
    return out


INITIALIZERS = {
    "normal_0.02": jax.nn.initializers.normal(0.02),
    "lecun_normal": jax.nn.initializers.lecun_normal(),
    "lecun_normal_each": jax.nn.initializers.lecun_normal(batch_axis=(0,)),
    "ones": jax.nn.initializers.ones,
}


def _fold(root, scope: tuple, count: int):
    m = hashlib.sha1()
    for x in (*scope, count):
        m.update(x.encode() if isinstance(x, str) else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def weights(config: dict, model_seed: int) -> dict:
    """Every weight, float32, on the device, in one jitted call from the seed;
    and each expert layer's selection bias, zero as at the first step."""
    spec = table(config)
    experts = routed(config)[0]

    def make(root):
        out = {
            name: INITIALIZERS[init](_fold(root, path[:-1], count), shape, jnp.float32)
            for name, (path, count, shape, init) in spec.items()
        }
        for i in range(config["num_dense_layers"], config["num_hidden_layers"]):
            out[f"{i}.bias"] = jnp.zeros((experts,), jnp.float32)
        return out

    return jax.jit(make)(jax.random.key(model_seed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def route(w: dict, i: int, m, c: dict, mm):
    """A layer's choice: ``idx`` [S, k] and the weights ``wt`` [S, k]."""
    scores = jax.nn.sigmoid(mm(m, w[f"{i}.router"]))
    _, idx = jax.lax.top_k(scores + w[f"{i}.bias"], c["num_experts_per_tok"])
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if c["route_norm"]:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    return idx, wt * c["route_scale"]


def _swiglu(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def experts_part(w: dict, i: int, m, c: dict, mm):
    """What the held experts add for the tokens that chose them."""
    _, held, offset = routed(c)
    idx, wt = route(w, i, m, c, mm)
    out = jnp.zeros_like(m)
    for e in range(held):
        mine = jnp.sum(jnp.where(idx == offset + e, wt, 0.0), axis=-1)  # 0 where not chosen
        out += mine[:, None] * _swiglu(
            m, w[f"{i}.experts_gate"][e], w[f"{i}.experts_up"][e], w[f"{i}.experts_down"][e], mm)
    return out


def mlp(w: dict, i: int, m, c: dict, mm):
    shared = _swiglu(m, w[f"{i}.gate"], w[f"{i}.up"], w[f"{i}.down"], mm)
    if i < c["num_dense_layers"]:
        return shared
    return shared + experts_part(w, i, m, c, mm)


def attention(w: dict, i: int, a, kind: str, c: dict, low, mm):
    s = a.shape[0]
    heads, kv_heads, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    q = _rms(mm(a, w[f"{i}.q"]).reshape(s, heads, hd), w[f"{i}.q_norm"], eps)
    k = _rms(mm(a, w[f"{i}.k"]).reshape(s, kv_heads, hd), w[f"{i}.k_norm"], eps)
    v = mm(a, w[f"{i}.v"]).reshape(s, kv_heads, hd)
    if kind == "sliding_attention":
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    elif kind != "full_attention":
        raise ValueError(f"unknown layer type {kind!r}")
    q = low(q).reshape(s, kv_heads, heads // kv_heads, hd)  # heads j*group.. share kv head j
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, s)
        qpos = jnp.arange(start, stop)
        keep = kpos[None, :] <= qpos[:, None]
        if kind == "sliding_attention":
            keep &= kpos[None, :] > qpos[:, None] - c["sliding_window"]
        scores = jnp.einsum("qjgd,kjd->jgqk", q[start:stop], low(k)) * hd**-0.5
        p = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("jgqk,kjd->qjgd", low(p), low(v)))
    o = jnp.concatenate(outs, axis=0).reshape(s, heads * hd)
    return mm(o * jax.nn.sigmoid(mm(a, w[f"{i}.g"])), w[f"{i}.o"])


def sequence_nll(w: dict, ids, config: dict, operands: str | None = None):
    """Sum over t < S - 1 of -log p(ids[t + 1] | ids[..t]) for one sequence.
    ``operands`` is for the control alone: a type below float32 that both
    operands of every matrix product are rounded to, the sums staying float32."""
    c = config
    if c.get("hidden_act", "silu") != "silu" or c.get("tie_word_embeddings") or c.get(
            "score_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference is the silu, sigmoid-routed, untied-head layer")
    low, mm = _matmul(operands)
    eps, s = c["rms_norm_eps"], ids.shape[0]
    h = w["embed"][ids]
    if c.get("mup_enabled"):
        h = h * c["hidden_size"] ** 0.5
    for i, kind in enumerate(kinds(c)):
        a = attention(w, i, _rms(h, w[f"{i}.input_norm"], eps), kind, c, low, mm)
        h = h + _rms(a, w[f"{i}.post_attn_norm"], eps)
        f = mlp(w, i, _rms(h, w[f"{i}.pre_mlp_norm"], eps), c, mm)
        h = h + _rms(f, w[f"{i}.post_mlp_norm"], eps)
    h = _rms(h, w["norm"], eps)
    total = jnp.float32(0.0)
    for start in range(0, s - 1, LOSS_BLOCK):
        stop = min(start + LOSS_BLOCK, s - 1)
        logits = mm(h[start:stop], w["head"].T)
        picked = jnp.take_along_axis(logits, ids[start + 1:stop + 1, None], axis=-1)[:, 0]
        total += jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)
    return total


def first_loss(config: dict, input_ids: np.ndarray, model_seed: int,
               operands: str | None = None) -> float:
    """The loss of the worker's first step: seeded weights, its first batch."""
    w = weights(config, model_seed)
    with jax.default_matmul_precision("highest"):
        nll = jax.jit(lambda w, ids: sequence_nll(w, ids, config, operands))
        total = sum(float(nll(w, jnp.asarray(row, jnp.int32))) for row in input_ids)
    rows, s = input_ids.shape
    return total / (rows * (s - 1))
