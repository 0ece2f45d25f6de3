"""Plain reference forward of Nemotron-H (NVIDIA Nemotron-3-Nano-30B-A3B,
`model_type: nemotron_h`).

Written from the published `config.json` keys and the family's published
modelling code, independent of the program: float32 `jax.numpy`, `highest`
matmul precision, no cache, no state pool, no kernels, no chunked form of the
recurrence, no sorting or grouping of tokens, no batching. One sequence in, the
log-probabilities of the next token out. H = hidden_size, eps =
layer_norm_epsilon; no projection has a bias, the convolution has one.

  x_0 = E[tokens]
  block i (one letter of hybrid_override_pattern):  x = x + f_i(rmsnorm(x, norm_i))
  f_i, "M" (Mamba-2; NH = mamba_num_heads, P = mamba_head_dim, Di = NH P,
            G = n_groups, N = ssm_state_size, K = conv_kernel):
      [z | xBC | dt] = h W_in        (widths Di | Di + 2 G N | NH)
      xBC = silu(b + sum_{j<K} w_j * xBC_{t-(K-1)+j})   (causal, depthwise, zeros
                                                         before position 0)
      x_t [NH, P], B_t [G, N], C_t [G, N] = split(xBC_t);  head h uses group h // (NH / G)
      dt_t = softplus(dt_t + dt_bias)  [NH];   A = -exp(A_log)  [NH]
      S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t[g]     S_{-1} = 0
      y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
      y = y * silu(z);  y = y * rsqrt(mean over each of the G groups of Di / G
                                      channels of y^2 + eps) * w_norm
      out = y W_out
    walked ONE POSITION AT A TIME (`lax.scan` over t): the program's prefill
    runs the chunked (SSD) form of the same sum, this does not.
  f_i, "E" (E = n_routed_experts as published, k = num_experts_per_tok):
      s = sigmoid(h W_r)                                    (E scores)
      chosen = top-k of (s + e_score_correction_bias)       (selection only; n_group =
                                                             topk_group = 1: no group step)
      weight_e = s_e / (sum of s over chosen + 1e-20) * routed_scaling_factor
      out = sum_{e in chosen} weight_e * W2_e relu(W1_e h)^2  +  Ws2 relu(Ws1 h)^2
      (the shared expert: every token, weight 1; no token is dropped)
  f_i, "*":  q, k, v = h Wq, h Wk, h Wv;  a = softmax(q k^T / sqrt(D) + causal mask) v
      (each kv head serves num_attention_heads / num_key_value_heads query
      heads; NO rotary embedding, no position signal of any kind: the family's
      published modelling code applies none);  a Wo
  logits = rmsnorm(x, norm_f) W_head                        (untied head)

`experts_held = {first, count, of}` in the document is the chip's share of
every expert layer (model-configs guide, section 4): the router and its top-k
are over all `of` experts, the parameter tree holds experts `first .. first +
count` alone (stored expert j is the router's expert first + j), and an
assignment to an expert outside the share contributes nothing.

Departures from the published code, for memory and compile time only: the sum
over a token's chosen experts is a walk over the held experts (`lax.scan`),
each applied to every token and kept where the token chose it; each block is
one jitted call on its own layer's weights, cast to float32 as they are used;
attention runs in query blocks; the head is taken at one position, in
vocabulary blocks. The published code keeps the router's scores, the step and
the state in the model's dtype unless told otherwise; here, as in the program
(`assumed` in the configuration's file), they are float32, like everything
else.

The parameter tree is the program's (`models/nemotron_h.init_params` leaf
names), every group stacked in layer order over the blocks of its kind: embed
[V,H]; ssm_layers.* (mixer_norm, in_proj [H, Di + Di + 2GN] = W_in's z and xBC
columns, dt_proj [H, NH] = its dt columns, conv_w [K, Di + 2GN], conv_b,
dt_bias [NH], a_log_head [NH], d_skip [NH], gate_norm [Di], out_proj [Di, H]);
attn_layers.* (mixer_norm, wq, wk, wv, wo); moe_layers.* (mlp_norm, router
[H,E], expert_bias [E] float32, w1 [held, H, >= I] (the program stores zero
columns past I up to whole 128-lane tiles: dropped here), w2 [held, I, H], w_up
[H, Is], w_down [Is, H]); final_norm [H]; lm_head [H, V].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512
VOCAB_BLOCK = 16384


def settings(doc: dict) -> dict:
    """The numbers the forward needs, from a published-config document."""
    held = doc.get("experts_held")
    heads = doc["num_attention_heads"]
    return {
        "pattern": doc["hybrid_override_pattern"],
        "eps": doc.get("layer_norm_epsilon", doc.get("norm_eps", 1e-5)),
        "m_heads": doc["mamba_num_heads"],
        "m_dim": doc["mamba_head_dim"],
        "groups": doc["n_groups"],
        "state": doc["ssm_state_size"],
        "conv": doc.get("conv_kernel", 4),
        "experts": held["of"] if held else doc["n_routed_experts"],
        "top_k": doc["num_experts_per_tok"],
        "norm_topk": bool(doc.get("norm_topk_prob", True)),
        "scaling": float(doc.get("routed_scaling_factor", 1.0)),
        # the stored experts are the router's first .. first + count
        "held": (held["first"], held["count"]) if held else None,
        "heads": heads,
        "kv_heads": doc.get("num_key_value_heads", heads),
        "head_dim": doc.get("head_dim") or doc["hidden_size"] // heads,
        # what the recurrent state is rounded to between two steps (a control:
        # next_token_logprobs(state_dtype=)); float32 = not at all
        "state_dtype": "float32",
    }


def _f32(a):
    return a.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _mamba(h, lp, s):
    T = h.shape[0]
    NH, P, G, N, K = s["m_heads"], s["m_dim"], s["groups"], s["state"], s["conv"]
    Di = NH * P
    z, xbc = jnp.split(h @ _f32(lp["in_proj"]), [Di], axis=-1)
    dt = jax.nn.softplus(h @ _f32(lp["dt_proj"]) + _f32(lp["dt_bias"]))       # [T, NH]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc])
    w = _f32(lp["conv_w"])
    xbc = jax.nn.silu(_f32(lp["conv_b"]) + sum(w[j] * padded[j:j + T] for j in range(K)))
    x = xbc[:, :Di].reshape(T, NH, P)
    b_mat = xbc[:, Di:Di + G * N].reshape(T, G, N)
    c_mat = xbc[:, Di + G * N:].reshape(T, G, N)
    a = -jnp.exp(_f32(lp["a_log_head"]))                                      # [NH]
    d = _f32(lp["d_skip"])

    def step(S, at):
        x_t, dt_t, b_t, c_t = at
        b_h = jnp.repeat(b_t, NH // G, axis=0)                                # [NH, N]
        c_h = jnp.repeat(c_t, NH // G, axis=0)
        S = (jnp.exp(dt_t * a)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        if s["state_dtype"] != "float32":
            # (the barrier keeps XLA:TPU from folding the two conversions into none)
            S = lax.optimization_barrier(S.astype(s["state_dtype"])).astype(jnp.float32)
        return S, jnp.sum(S * c_h[:, None, :], axis=-1) + d[:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((NH, P, N), jnp.float32), (x, dt, b_mat, c_mat))
    y = y.reshape(T, Di) * jax.nn.silu(z)
    y = y.reshape(T, G, Di // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + s["eps"])
    return (y.reshape(T, Di) * _f32(lp["gate_norm"])) @ _f32(lp["out_proj"])


def _attention(h, lp, s):
    T = h.shape[0]
    NH, KH, D = s["heads"], s["kv_heads"], s["head_dim"]
    q = (h @ _f32(lp["wq"])).reshape(T, NH, D)
    k = jnp.repeat((h @ _f32(lp["wk"])).reshape(T, KH, D), NH // KH, axis=1)
    v = jnp.repeat((h @ _f32(lp["wv"])).reshape(T, KH, D), NH // KH, axis=1)
    kpos = jnp.arange(T)
    outs = []
    for start in range(0, T, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = jnp.arange(start, start + qb.shape[0])
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(D))
        scores = jnp.where((kpos[None, :] <= qpos[:, None])[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs, axis=0).reshape(T, NH * D) @ _f32(lp["wo"])


def _experts(h, lp, s):
    E, k = s["experts"], s["top_k"]
    scores = jax.nn.sigmoid(h @ _f32(lp["router"]))                           # [T, E]
    _, chosen = lax.top_k(scores + _f32(lp["expert_bias"]), k)                # [T, k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    weight = jnp.where(picked, scores, 0.0)
    if s["norm_topk"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * s["scaling"]
    first, count = s["held"] or (0, E)
    width = lp["w2"].shape[1]

    def expert(out, j):
        # (the program stores W1 with zero columns past the expert's width)
        y = _relu2(h @ _f32(lp["w1"][j][:, :width])) @ _f32(lp["w2"][j])
        return out + weight[:, first + j, None] * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(count))
    return out + _relu2(h @ _f32(lp["w_up"])) @ _f32(lp["w_down"])


_BLOCKS = {"M": (_mamba, "ssm_layers", "mixer_norm"),
           "*": (_attention, "attn_layers", "mixer_norm"),
           "E": (_experts, "moe_layers", "mlp_norm")}


@functools.partial(jax.jit, static_argnames=("kind", "frozen"))
def _block(x, lp, kind, frozen):
    s = dict(frozen)
    f, _, norm = _BLOCKS[kind]
    return x + f(_rmsnorm(x, _f32(lp[norm]), s["eps"]), lp, s)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, last, norm, head, eps):
    h = _rmsnorm(x[last], _f32(norm), eps)
    V = head.shape[1]
    logits = jnp.concatenate([
        h @ _f32(head[:, b:b + VOCAB_BLOCK]) for b in range(0, V, VOCAB_BLOCK)
    ])
    return logits - jax.scipy.special.logsumexp(logits)


def next_token_logprobs(params, doc: dict, tokens, pad_to: int = 0, experts_held=None,
                        state_dtype=None):
    """log p(next token | tokens) as a float32 [V] array. `pad_to` pads the
    sequence on the right (causally inert) so that growing sequences share one
    compile. `experts_held` (first, count) overrides the document's share: the
    parameter tree then holds those experts alone. `state_dtype` rounds the
    recurrent state to that type between two steps (a control of the
    comparison: what a server that keeps its state in bfloat16 computes)."""
    n = len(tokens)
    ids = jnp.zeros((max(n, pad_to),), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    s = settings(doc)
    if experts_held is not None:
        s["held"] = tuple(experts_held)
    if state_dtype is not None:
        s["state_dtype"] = jnp.dtype(state_dtype).name
    frozen = tuple(sorted(s.items()))
    seen = {"M": 0, "*": 0, "E": 0}
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][ids])
        for kind in s["pattern"]:
            group = params[_BLOCKS[kind][1]]
            lp = {name: a[seen[kind]] for name, a in group.items()}
            x = _block(x, lp, kind, frozen)
            seen[kind] += 1
        return _head(x, n - 1, params["final_norm"], params["lm_head"], s["eps"])
