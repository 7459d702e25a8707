"""The plain reference of the ``lfm2_moe`` configurations (Liquid AI's
LFM2-24B-A2B): the published layer's forward pass and loss in ``jax.numpy``
and float32 at ``highest`` matmul precision, on weights it makes itself from
the seed.

Nothing of the program is imported and nothing it made is read: no kernel,
no grouped product, no ``hypha_tpu``. The layer is the one of the source's
``modeling_lfm2_moe.py`` (RMSNorm throughout, ``x / sqrt(mean(x^2) + eps) *
w``; no bias anywhere):

* ``h = E[ids]``; a layer is ``h += operator(RMSNorm(h))``, then
  ``h += f(RMSNorm(h))``;
* a ``conv`` operator: ``[B, C, x] = split3(u W_in)`` in that order, ``z = B *
  x``, ``c_t = sum_j w[j] z_{t - (L - 1) + j}`` over the ``conv_L_cache`` taps
  of each channel with ``z`` zero before the sequence's start, ``y = C * c``,
  then the output projection; the gates carry no activation;
* a ``full_attention`` operator: ``q, k, v`` projected; ``q`` and ``k``
  RMS-normed over the head with a learned weight, then rotate-half RoPE over
  the whole head (``rope_parameters.rope_theta``); ``softmax(q k^T /
  sqrt(head_dim))`` under the causal mask, query heads in groups to a key
  head; the output projection;
* ``f``: a SwiGLU of ``intermediate_size`` in the leading dense layers; after
  them ``s = sigmoid(m W_r)`` over all the layer's experts, ``idx = top_k(s +
  b)`` with ``b`` the selection bias (``use_expert_bias``; zero at the first
  step), ``w = s[idx]``, divided by its sum + 1e-6 where ``norm_topk_prob``
  and scaled by ``routed_scaling_factor``, and ``f = sum_i w_i
  expert_idx_i(m)``: no shared expert;
* ``logits = RMSNorm(h) E^T``: the final norm is the source's
  ``embedding_norm``, the head the embedding matrix.

**Where this departs from the source**, each the configuration file's
(``reduced``, ``assumed``): ``head_dim`` is read from the file (the source's
class derives hidden_size / heads); the head is tied; the configuration holds
``num_experts`` of the layer's ``share.experts_routed`` experts, from
``share.expert_offset`` on: the router keeps its full width and the sum over
chosen experts runs over the held ones only, as in the program;
``layers_run`` names the source layers that are run and ``layer_types`` (the
source's, whole) gives each its kind; ids, logits and loss are over the
vocabulary's slice. The routed part is a loop over the held experts, each run
densely on every token with a weight that is zero where the token did not
choose it: plain, and eight times the routed work.

The loss is the program's: the mean cross-entropy of position t's logits
against token t + 1 over the first S - 1 positions of every row. Weights are
data: ``table`` says where the worker's flax module keeps each, in what shape
and from which initializer, and ``weights`` replays flax's key derivation
(``tests/perfbench/test_reference_lfm2_moe.py`` holds them to the module's bit
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
ROUTE_EPS = 1e-6  # the source's, in its renormalisation


def routed(c: dict) -> tuple[int, int, int]:
    """(experts the router scores, experts held here, the first held)."""
    share = c.get("share", {})
    return (share.get("experts_routed", c["num_experts"]), c["num_experts"],
            share.get("expert_offset", 0))


def kinds(c: dict) -> list[str]:
    """The operator of each layer that is run."""
    run = c.get("layers_run", range(c["num_hidden_layers"]))
    return [c["layer_types"][source] for source in run]


def table(c: dict) -> dict[str, tuple]:
    """weight -> (path in the worker's ``params`` tree, number among the
    parameters its flax scope draws, shape, initializer). The scope is the
    path without its last element."""
    d, hd, v = c["hidden_size"], c["head_dim"], c["vocab_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    f, taps = c["moe_intermediate_size"], c["conv_L_cache"]
    experts, held, _ = routed(c)
    out = {"embed": (("embed_tokens",), 1, (v, d), "normal_0.02")}
    for i, kind in enumerate(kinds(c)):
        layer, ffn = f"layers_{i}", (f"layers_{i}", "feed_forward")
        out[f"{i}.operator_norm"] = ((layer, "operator_norm", "weight"), 1, (d,), "ones")
        if kind == "conv":
            conv = (layer, "conv")
            out |= {
                f"{i}.taps": ((*conv, "conv_weight"), 1, (taps, d), "lecun_normal"),
                f"{i}.in": ((*conv, "in_proj", "kernel"), 1, (d, 3 * d), "lecun_normal"),
                f"{i}.out": ((*conv, "out_proj", "kernel"), 1, (d, d), "lecun_normal"),
            }
        elif kind == "full_attention":
            attn = (layer, "self_attn")
            out |= {
                f"{i}.q": ((*attn, "q_proj", "kernel"), 1, (d, q), "lecun_normal"),
                f"{i}.k": ((*attn, "k_proj", "kernel"), 1, (d, kv), "lecun_normal"),
                f"{i}.v": ((*attn, "v_proj", "kernel"), 1, (d, kv), "lecun_normal"),
                f"{i}.q_norm": ((*attn, "q_layernorm"), 1, (hd,), "ones"),
                f"{i}.k_norm": ((*attn, "k_layernorm"), 2, (hd,), "ones"),
                f"{i}.out": ((*attn, "out_proj", "kernel"), 1, (q, d), "lecun_normal"),
            }
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        out[f"{i}.ffn_norm"] = ((layer, "ffn_norm", "weight"), 1, (d,), "ones")
        if i < c["num_dense_layers"]:
            wide = c["intermediate_size"]
            out |= {
                f"{i}.gate": ((*ffn, "gate_proj", "kernel"), 1, (d, wide), "lecun_normal"),
                f"{i}.up": ((*ffn, "up_proj", "kernel"), 1, (d, wide), "lecun_normal"),
                f"{i}.down": ((*ffn, "down_proj", "kernel"), 1, (wide, d), "lecun_normal"),
            }
        else:
            out |= {
                f"{i}.router": ((*ffn, "router"), 1, (d, experts), "lecun_normal"),
                f"{i}.experts_gate": ((*ffn, "experts_gate"), 2, (held, d, f), "lecun_normal_each"),
                f"{i}.experts_up": ((*ffn, "experts_up"), 3, (held, d, f), "lecun_normal_each"),
                f"{i}.experts_down": ((*ffn, "experts_down"), 4, (held, f, d), "lecun_normal_each"),
            }
    out["norm"] = (("embedding_norm", "weight"), 1, (d,), "ones")
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


def conv_operator(w: dict, i: int, u, mm):
    """The gated short convolution of layer ``i`` on ``u`` [S, width]."""
    gate_b, gate_c, x = jnp.split(mm(u, w[f"{i}.in"]), 3, axis=-1)
    z = gate_b * x
    taps = w[f"{i}.taps"]  # [L, width]: the last weighs the current position
    last = taps.shape[0] - 1
    conv = jnp.zeros_like(z)
    for j in range(taps.shape[0]):
        back = last - j  # z_{t - back}, zero before the start
        shifted = jnp.concatenate([jnp.zeros_like(z[:back]), z[: z.shape[0] - back]], axis=0)
        conv += taps[j] * shifted
    return mm(gate_c * conv, w[f"{i}.out"])


def attention(w: dict, i: int, a, c: dict, low, mm):
    s = a.shape[0]
    heads, kv_heads, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["norm_eps"], c["rope_parameters"]["rope_theta"]
    q = _rope(_rms(mm(a, w[f"{i}.q"]).reshape(s, heads, hd), w[f"{i}.q_norm"], eps), theta)
    k = _rope(_rms(mm(a, w[f"{i}.k"]).reshape(s, kv_heads, hd), w[f"{i}.k_norm"], eps), theta)
    v = mm(a, w[f"{i}.v"]).reshape(s, kv_heads, hd)
    q = low(q).reshape(s, kv_heads, heads // kv_heads, hd)  # heads j*group.. share kv head j
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, s)
        keep = kpos[None, :] <= jnp.arange(start, stop)[:, None]
        scores = jnp.einsum("qjgd,kjd->jgqk", q[start:stop], low(k)) * hd**-0.5
        p = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("jgqk,kjd->qjgd", low(p), low(v)))
    return mm(jnp.concatenate(outs, axis=0).reshape(s, heads * hd), w[f"{i}.out"])


def route(w: dict, i: int, m, c: dict, mm):
    """A layer's choice: ``idx`` [S, k] and the weights ``wt`` [S, k]."""
    scores = jax.nn.sigmoid(mm(m, w[f"{i}.router"]))
    chooses = scores + w[f"{i}.bias"] if c["use_expert_bias"] else scores
    _, idx = jax.lax.top_k(chooses, c["num_experts_per_tok"])
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if c["norm_topk_prob"]:
        wt = wt / (wt.sum(-1, keepdims=True) + ROUTE_EPS)
    return idx, wt * c["routed_scaling_factor"]


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


def feed_forward(w: dict, i: int, m, c: dict, mm):
    if i < c["num_dense_layers"]:
        return _swiglu(m, w[f"{i}.gate"], w[f"{i}.up"], w[f"{i}.down"], mm)
    return experts_part(w, i, m, c, mm)


def hidden(w: dict, ids, config: dict, operands: str | None = None):
    """The final norm's output for one sequence, [S, width]."""
    c = config
    if c.get("conv_bias"):
        raise ValueError("this reference is the layer without a convolution bias")
    low, mm = _matmul(operands)
    eps = c["norm_eps"]
    h = w["embed"][ids]
    for i, kind in enumerate(kinds(c)):
        u = _rms(h, w[f"{i}.operator_norm"], eps)
        h = h + (conv_operator(w, i, u, mm) if kind == "conv" else attention(w, i, u, c, low, mm))
        h = h + feed_forward(w, i, _rms(h, w[f"{i}.ffn_norm"], eps), c, mm)
    return _rms(h, w["norm"], eps)


def sequence_nll(w: dict, ids, config: dict, operands: str | None = None):
    """Sum over t < S - 1 of -log p(ids[t + 1] | ids[..t]) for one sequence.
    ``operands`` is for the control alone: a type below float32 that both
    operands of every matrix product are rounded to, the sums staying float32."""
    _, mm = _matmul(operands)
    h, s = hidden(w, ids, config, operands), ids.shape[0]
    total = jnp.float32(0.0)
    for start in range(0, s - 1, LOSS_BLOCK):
        stop = min(start + LOSS_BLOCK, s - 1)
        logits = mm(h[start:stop], w["embed"].T)
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
