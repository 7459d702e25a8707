"""The plain reference of the ``nemotron_h`` configurations (NVIDIA's
Nemotron-H stack, arXiv:2504.03624, with Mamba-2's layer, arXiv:2405.21060):
the layers' forward pass and loss in ``jax.numpy`` and float32 at ``highest``
matmul precision, on weights it makes itself from the seed.

Nothing of the program is imported and nothing it made is read: no kernel, no
chunked scan, no grouped product, no ``hypha_tpu``.

* ``h = E[ids]``; a block is one norm and one part, ``h += part(RMSNorm(h))``,
  ``RMSNorm(x) = w x / sqrt(mean(x^2) + eps)``; the part of source layer ``i``
  by the ``i``-th letter of ``hybrid_override_pattern``: ``M`` Mamba-2, ``*``
  attention, ``E`` experts; no position encoding; ``logits = RMSNorm(h) W_head``.
* ``M``: ``[z, xBC, dt] = u W_in`` (``d_inner``, ``d_inner + 2 G N``, one a
  head); ``xBC = silu(conv(xBC) + b)``, depthwise and causal over
  ``conv_kernel`` taps (the last weighs the current position); ``[x, B, C] =
  xBC``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; for head ``h`` of
  group ``g``, position by position: ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t
  B_t^T``, ``y_t = H_t C_t + D x_t``; ``y = w GroupRMSNorm(y silu(z))``, the gate
  before the norm and the norm over each group's ``d_inner / G`` channels;
  ``out = y W_out``.
* ``*``: ``q, k, v`` projected, causal softmax at ``1 / sqrt(head_dim)``,
  ``num_attention_heads / num_key_value_heads`` query heads to a key head, the
  output projection.
* ``E``: ``s = sigmoid(u W_r)`` over all the layer's experts, ``idx = top_k(s +
  b)`` with ``b`` the selection bias (zero at the first step), ``w = s[idx]``
  divided by their sum + 1e-20 (``norm_topk_prob``) and times
  ``routed_scaling_factor``; ``out = sum_i w_i W2_i relu(W1_i u)^2 + W2_s
  relu(W1_s u)^2``.

**The share.** The configuration holds ``n_routed_experts`` of the layer's
``share.experts_routed`` experts, from ``share.expert_offset`` on: the router
keeps its full width, and the sum over chosen experts runs over the held ones
only, as in the program. ``layers_run`` names the source layers that are run;
``hybrid_override_pattern`` (the source's, whole) gives each its part, and its
length the depth by which each part's output projection is drawn smaller
(``rescale_prenorm_residual``). The routed part is a loop over the held
experts, each run densely on every token with a weight that is zero where the
token did not choose it.

The loss is the program's: the mean cross-entropy of position t's logits
against token t + 1 over the first S - 1 positions of every row. Weights are
data: ``table`` says where the worker's flax module keeps each, in what shape
and from which initializer, and ``weights`` replays flax's key derivation
(``tests/perfbench/test_reference_nemotron_h.py`` holds them to the module's
within an ulp). One sequence at a time, attention in blocks of queries and the
loss in blocks of positions, so that 8192 positions fit a chip beside 2.1 GB
of weights.

Two arguments are for probes and controls, never for the number a cell is held
to: ``operands``, a type below float32 that both operands of every matrix
product and the ``x``, ``B`` and ``C`` that the recurrence multiplies are
rounded to (the sums stay float32), and ``choice``, each expert layer's experts
a token given from outside (``chosen`` returns a pass's own), the weights still
from this pass's scores: that takes a token whose sixth and seventh scores lie
closer than another precision's rounding out of a difference between two
passes (``tests/perfbench/chip_probe_nemotron_h.py``).
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024  # positions a block of logits
QUERY_BLOCK = 1024  # queries a block of attention scores: [kv, group, 1024, S] f32
PARTS = {"M": "mamba2", "*": "full_attention", "E": "experts"}


def routed(c: dict) -> tuple[int, int, int]:
    """(experts the router scores, experts held here, the first held)."""
    share = c.get("share", {})
    return (share.get("experts_routed", c["n_routed_experts"]), c["n_routed_experts"],
            share.get("expert_offset", 0))


def kinds(c: dict) -> list[str]:
    """The part of each layer that is run."""
    pattern = c["hybrid_override_pattern"]
    return [PARTS[pattern[i]] for i in c.get("layers_run", range(len(pattern)))]


def mamba_sizes(c: dict) -> tuple[int, int, int, int]:
    """(heads, head size, state size, groups)."""
    return c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"], c["n_groups"]


def table(c: dict) -> dict[str, tuple]:
    """weight -> (path in the worker's ``params`` tree, number among the
    parameters its flax scope draws, shape, initializer). The scope is the
    path without its last element."""
    d, hd, v = c["hidden_size"], c["head_dim"], c["vocab_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    heads, p, n, g = mamba_sizes(c)
    di = heads * p
    wide = di + 2 * g * n
    f, fs = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    experts, held, _ = routed(c)
    out = {"embed": (("embed_tokens",), 1, (v, d), "normal_0.02")}
    for j, what in enumerate(kinds(c)):
        layer, mixer = f"layers_{j}", (f"layers_{j}", "mixer")
        out[f"{j}.norm"] = ((layer, "norm", "weight"), 1, (d,), "ones")
        if what == "mamba2":
            out |= {
                f"{j}.in": ((*mixer, "in_proj", "kernel"), 1, (d, di + wide + heads), "lecun_normal"),
                f"{j}.taps": ((*mixer, "conv_weight"), 1, (c["conv_kernel"], wide), "lecun_normal"),
                f"{j}.taps.b": ((*mixer, "conv_bias"), 2, (wide,), "zeros"),
                f"{j}.dt_bias": ((*mixer, "dt_bias"), 3, (heads,), "dt_bias"),
                f"{j}.a_log": ((*mixer, "A_log"), 4, (heads,), "a_log"),
                f"{j}.d": ((*mixer, "D"), 5, (heads,), "ones"),
                f"{j}.gated_norm": ((*mixer, "norm"), 6, (di,), "ones"),
                f"{j}.out": ((*mixer, "out_proj", "kernel"), 1, (di, d), "lecun_normal_out"),
            }
        elif what == "full_attention":
            out |= {
                f"{j}.q": ((*mixer, "q_proj", "kernel"), 1, (d, q), "lecun_normal"),
                f"{j}.k": ((*mixer, "k_proj", "kernel"), 1, (d, kv), "lecun_normal"),
                f"{j}.v": ((*mixer, "v_proj", "kernel"), 1, (d, kv), "lecun_normal"),
                f"{j}.o": ((*mixer, "o_proj", "kernel"), 1, (q, d), "lecun_normal_out"),
            }
        else:
            out |= {
                f"{j}.router": ((*mixer, "router"), 1, (d, experts), "lecun_normal"),
                f"{j}.experts_up": ((*mixer, "experts_up"), 2, (held, d, f), "lecun_normal_each"),
                f"{j}.experts_down": ((*mixer, "experts_down"), 3, (held, f, d), "lecun_normal_each_out"),
                f"{j}.up": ((*mixer, "shared_experts", "up_proj", "kernel"), 1, (d, fs), "lecun_normal"),
                f"{j}.down": ((*mixer, "shared_experts", "down_proj", "kernel"), 1, (fs, d), "lecun_normal_out"),
            }
    out["norm"] = (("norm_f", "weight"), 1, (d,), "ones")
    out["head"] = (("lm_head",), 2, (v, d), "normal_0.02")
    return out


def initializers(c: dict) -> dict:
    """By name. A part's output projection is drawn ``1 / sqrt(depth)`` smaller,
    ``depth`` the source's (``rescale_prenorm_residual``)."""
    lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])

    def dt_bias(key, shape, dtype):
        """The inverse softplus of a step drawn log-uniformly in
        [``time_step_min``, ``time_step_max``] and floored at ``time_step_floor``."""
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, c["time_step_floor"])
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key, shape, dtype):
        """log(1 ... heads), one a head."""
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(dtype)

    def scaled(scale, **kw):
        return jax.nn.initializers.variance_scaling(scale, "fan_in", "truncated_normal", **kw)

    smaller = 1.0 / len(c["hybrid_override_pattern"]) if c.get("rescale_prenorm_residual") else 1.0
    return {
        "normal_0.02": jax.nn.initializers.normal(0.02),
        "lecun_normal": scaled(1.0),
        "lecun_normal_out": scaled(smaller),
        "lecun_normal_each": scaled(1.0, batch_axis=(0,)),
        "lecun_normal_each_out": scaled(smaller, batch_axis=(0,)),
        "ones": jax.nn.initializers.ones,
        "zeros": jax.nn.initializers.zeros,
        "dt_bias": dt_bias,
        "a_log": a_log,
    }


def _fold(root, scope: tuple, count: int):
    m = hashlib.sha1()
    for x in (*scope, count):
        m.update(x.encode() if isinstance(x, str) else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def weights(config: dict, model_seed: int) -> dict:
    """Every weight, float32, on the device, in one jitted call from the seed;
    and each expert layer's selection bias, zero as at the first step."""
    spec, inits = table(config), initializers(config)
    experts = routed(config)[0]

    def make(root):
        out = {
            name: inits[init](_fold(root, path[:-1], count), shape, jnp.float32)
            for name, (path, count, shape, init) in spec.items()
        }
        for j, what in enumerate(kinds(config)):
            if what == "experts":
                out[f"{j}.bias"] = jnp.zeros((experts,), jnp.float32)
        return out

    return jax.jit(make)(jax.random.key(model_seed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


class Plain:
    """float32 throughout. ``operands`` is for the control alone: a type below
    float32 that both operands of every matrix product, and the ``x``, ``B``
    and ``C`` the recurrence multiplies, are rounded to, the sums staying
    float32."""

    def __init__(self, operands: str | None = None):
        self.operands = operands

    def low(self, a):
        return a.astype(self.operands).astype(jnp.float32) if self.operands else a

    def mm(self, a, b):
        return self.low(a) @ self.low(b)


def mamba2(w: dict, j: int, u, c: dict, p):
    """Layer ``j`` on ``u`` [S, width] -> [S, width]."""
    heads, hp, n, g = mamba_sizes(c)
    s, di = u.shape[0], heads * hp
    z, xbc, dt = jnp.split(p.mm(u, w[f"{j}.in"]), [di, 2 * di + 2 * g * n], axis=-1)
    taps = w[f"{j}.taps"]  # [taps, channels]: the last weighs the current position
    last = taps.shape[0] - 1
    conv = jnp.zeros_like(xbc)
    for t in range(taps.shape[0]):
        back = last - t  # xbc_{t - back}, zero before the start
        conv += taps[t] * jnp.concatenate([jnp.zeros_like(xbc[:back]), xbc[: s - back]], axis=0)
    # the control rounds what the recurrence multiplies, as it does a product's operands
    x, b, cc = jnp.split(p.low(jax.nn.silu(conv + w[f"{j}.taps.b"])), [di, di + g * n], axis=-1)
    x = x.reshape(s, heads, hp)
    group_of = jnp.arange(heads) // (heads // g)  # head h reads group h // (heads / groups)
    b, cc = b.reshape(s, g, n)[:, group_of], cc.reshape(s, g, n)[:, group_of]  # [S, heads, N]
    dt = jax.nn.softplus(dt + w[f"{j}.dt_bias"])  # [S, heads]
    a = -jnp.exp(w[f"{j}.a_log"])  # [heads]

    def position(h, t):
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(position, jnp.zeros((heads, hp, n), jnp.float32), (x, dt, b, cc))
    y = y + w[f"{j}.d"][:, None] * x
    gated = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, g, di // g)
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + c["layer_norm_epsilon"])
    return p.mm(normed.reshape(s, di) * w[f"{j}.gated_norm"], w[f"{j}.out"])


def attention(w: dict, j: int, u, c: dict, p):
    s = u.shape[0]
    heads, kv_heads, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = p.low(p.mm(u, w[f"{j}.q"])).reshape(s, kv_heads, heads // kv_heads, hd)  # heads j*group.. share kv head j
    k = p.low(p.mm(u, w[f"{j}.k"])).reshape(s, kv_heads, hd)
    v = p.low(p.mm(u, w[f"{j}.v"])).reshape(s, kv_heads, hd)
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, s)
        keep = kpos[None, :] <= jnp.arange(start, stop)[:, None]
        scores = jnp.einsum("qjgd,kjd->jgqk", q[start:stop], k) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("jgqk,kjd->qjgd", p.low(probs), v))
    return p.mm(jnp.concatenate(outs, axis=0).reshape(s, heads * hd), w[f"{j}.o"])


def route(w: dict, j: int, u, c: dict, p, idx=None):
    """A layer's choice: ``idx`` [S, k], this pass's own where none is given,
    and the weights ``wt`` [S, k], always from this pass's own scores."""
    scores = jax.nn.sigmoid(p.mm(u, w[f"{j}.router"]))
    if idx is None:
        _, idx = jax.lax.top_k(scores + w[f"{j}.bias"], c["num_experts_per_tok"])
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if c["norm_topk_prob"]:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    return idx, wt * c["routed_scaling_factor"]


def _relu2(u, up, down, mm):
    return mm(jnp.square(jax.nn.relu(mm(u, up))), down)


def routed_part(w: dict, j: int, u, c: dict, p, idx=None):
    """What the held experts add for the tokens that chose them, and the choice."""
    _, held, offset = routed(c)
    idx, wt = route(w, j, u, c, p, idx)
    out = jnp.zeros_like(u)
    for e in range(held):
        mine = jnp.sum(jnp.where(idx == offset + e, wt, 0.0), axis=-1)  # 0 where not chosen
        out += mine[:, None] * _relu2(u, w[f"{j}.experts_up"][e], w[f"{j}.experts_down"][e], p.mm)
    return out, idx


def experts(w: dict, j: int, u, c: dict, p, idx=None):
    out, idx = routed_part(w, j, u, c, p, idx)
    return _relu2(u, w[f"{j}.up"], w[f"{j}.down"], p.mm) + out, idx


def _run(w: dict, ids, config: dict, p, choice=None):
    """The final norm's output [S, width] and each expert layer's choice."""
    c = config
    if c.get("mlp_hidden_act", "relu2") != "relu2" or c.get("mamba_hidden_act", "silu") != "silu" or c.get(
            "tie_word_embeddings") or c.get("n_group", 1) != 1:
        raise ValueError("this reference is the relu2, silu, one-group-routed, untied-head layer")
    eps, made = c["layer_norm_epsilon"], {}
    h = w["embed"][ids]
    for j, what in enumerate(kinds(c)):
        u = _rms(h, w[f"{j}.norm"], eps)
        if what == "mamba2":
            out = mamba2(w, j, u, c, p)
        elif what == "full_attention":
            out = attention(w, j, u, c, p)
        else:
            out, made[j] = experts(w, j, u, c, p, None if choice is None else choice[j])
        h = h + out
    return _rms(h, w["norm"], eps), made


def hidden(w: dict, ids, config: dict, operands: str | None = None, choice=None):
    """The final norm's output for one sequence, [S, width]. ``choice``: each
    expert layer's experts a token, ``{layer: [S, k]}``, where they are not to
    be chosen from this pass's own scores."""
    return _run(w, ids, config, Plain(operands), choice)[0]


def chosen(w: dict, ids, config: dict, operands: str | None = None) -> dict:
    """Each expert layer's experts a token, ``{layer: [S, k]}``."""
    return _run(w, ids, config, Plain(operands))[1]


def sequence_nll(w: dict, ids, config: dict, operands: str | None = None, choice=None):
    """Sum over t < S - 1 of -log p(ids[t + 1] | ids[..t]) for one sequence."""
    p = Plain(operands)
    h, s = hidden(w, ids, config, operands, choice), ids.shape[0]
    total = jnp.float32(0.0)
    for start in range(0, s - 1, LOSS_BLOCK):
        stop = min(start + LOSS_BLOCK, s - 1)
        logits = p.mm(h[start:stop], w["head"].T)
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
