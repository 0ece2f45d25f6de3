"""Plain reference forward of LFM2-MoE (LiquidAI LFM2-8B-A1B, `model_type:
lfm2_moe`).

Written from the published `config.json` keys and the family's published
modelling code, independent of the program: float32 `jax.numpy`, `highest`
matmul precision, no cache, no state pool, no kernels, no sorting or grouping
of tokens, no batching. One sequence in, the log-probabilities of the next
token out. H = hidden_size, eps = norm_eps, no projection has a bias.

  x_0 = E[tokens]
  layer i:  r = x ; x = r + mixer_i(rmsnorm(x, operator_norm_i))
            x = x + ffn_i(rmsnorm(x, ffn_norm_i))
  mixer_i, layer_types[i] == "conv" (L = conv_L_cache):
      [B, C, u] = h W_in           (W_in: H x 3H, split in three)
      g = B * u
      c_t = sum_{j<L} w_j * g_{t-(L-1)+j}     (causal, depthwise, zeros before
                                               position 0, no bias, NO activation)
      out = (C * c) W_out
  mixer_i, "full_attention":
      q, k, v = h Wq, h Wk, h Wv ; q = rmsnorm(q, q_layernorm) and k =
      rmsnorm(k, k_layernorm) over head_dim, per head, THEN rope (rotate-half
      over the whole head, base rope_theta); a = softmax(q k^T / sqrt(D) +
      causal mask) v (each kv head serves num_heads / num_kv_heads query
      heads); a Wo
  ffn_i, i < num_dense_layers:  W2 (silu(W1 h) * W3 h)
  ffn_i otherwise (E = num_experts, k = num_experts_per_tok):
      s = sigmoid(h W_gate)                     (E scores)
      chosen = top-k of (s + expert_bias)       (the bias decides the SELECTION only)
      weight_e = s_e / (sum of s over chosen + 1e-6) * routed_scaling_factor
      out = sum_{e in chosen} weight_e * W2_e (silu(W1_e h) * W3_e h)
      no shared expert; no token is dropped whatever the imbalance
  logits = rmsnorm(x, embedding_norm) E^T                      (tied head)

`experts_held = (first, count)` is the chip's share of every expert layer
(model-configs guide, section 4): the router and its top-k are over all E, an
assignment to an expert outside the share contributes nothing.

Departures from the published code, for memory and compile time only (the
mathematics is unchanged): the sum over a token's chosen experts is written as
a walk over the experts (`lax.scan`), each applied to every token and kept
where the token chose it, so that no token gathers a copy of its experts'
weights (4 x 44 MB a token in float32); each run of consecutive layers of one
kind is walked with `lax.scan` over its layer indices into the stacked bf16
weights, each layer cast to float32 as it is used; attention runs in query
blocks; the head is taken at one position, in vocabulary blocks. The published
code keeps the router's scores in the model's dtype; here, as in the program
(`assumed` in the configuration's file), they are float32.

The parameter tree is the program's (`models/lfm2.init_params` leaf names),
every group stacked in layer order over the layers of its kind: embed [V,H];
conv_layers.* (mixer_norm = operator_norm, in_proj [H,3H], conv_w [L,H],
out_proj [H,H]); attn_layers.* (mixer_norm, wq, wk, wv, wo, q_norm [D], k_norm
[D]); dense_ffn.* (mlp_norm = ffn_norm, w_gate = W1, w_up = W3, w_down = W2);
moe_ffn.* (mlp_norm, router = W_gate [H,E], expert_bias [E] float32, w13
[E,H,2I] = [W1 | W3], w2 [E,I,H]); final_norm = embedding_norm [H].
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
    heads = doc["num_attention_heads"]
    return {
        "kinds": tuple(doc["layer_types"]),
        "dense": doc.get("num_dense_layers", 0),
        "heads": heads,
        "kv_heads": doc.get("num_key_value_heads", heads),
        "head_dim": doc.get("head_dim") or doc["hidden_size"] // heads,
        "eps": doc.get("norm_eps", 1e-5),
        "theta": float(doc.get("rope_theta", 1000000.0)),
        "conv": doc.get("conv_L_cache", 3),
        "experts": doc["num_experts"],
        "top_k": doc["num_experts_per_tok"],
        "norm_topk": bool(doc.get("norm_topk_prob", True)),
        "use_bias": bool(doc.get("use_expert_bias", True)),
        "scaling": float(doc.get("routed_scaling_factor", 1.0)),
        "held": None,  # every expert; next_token_logprobs(experts_held=) cuts
    }


def _f32(a):
    return a.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def _rope(x, theta):
    # x [T, heads, D]: rotate-half over the whole head
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rotated * sin


def _attention(q, k, v, s):
    # q [T, NH, D], k/v [T, KH, D] -> [T, NH*D]; query blocks bound the scores
    T, NH, D = q.shape
    group = NH // s["kv_heads"]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(T)
    outs = []
    for start in range(0, T, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = jnp.arange(start, start + qb.shape[0])
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(D))
        scores = jnp.where((kpos[None, :] <= qpos[:, None])[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs, axis=0).reshape(T, NH * D)


def _attn_mixer(h, lp, s):
    T = h.shape[0]
    q = (h @ _f32(lp["wq"])).reshape(T, s["heads"], s["head_dim"])
    k = (h @ _f32(lp["wk"])).reshape(T, s["kv_heads"], s["head_dim"])
    v = (h @ _f32(lp["wv"])).reshape(T, s["kv_heads"], s["head_dim"])
    q = _rope(_rmsnorm(q, _f32(lp["q_norm"]), s["eps"]), s["theta"])
    k = _rope(_rmsnorm(k, _f32(lp["k_norm"]), s["eps"]), s["theta"])
    return _attention(q, k, v, s) @ _f32(lp["wo"])


def _conv_mixer(h, lp, s):
    T, L = h.shape[0], s["conv"]
    b_gate, c_gate, u = jnp.split(h @ _f32(lp["in_proj"]), 3, axis=-1)
    g = b_gate * u
    padded = jnp.concatenate([jnp.zeros((L - 1, g.shape[1]), jnp.float32), g])
    w = _f32(lp["conv_w"])
    # three shifted copies: tap j meets the row L-1-j steps back
    c = sum(w[j] * padded[j:j + T] for j in range(L))
    return (c_gate * c) @ _f32(lp["out_proj"])


def _dense_ffn(h, lp):
    return (jax.nn.silu(h @ _f32(lp["w_gate"])) * (h @ _f32(lp["w_up"]))) @ _f32(lp["w_down"])


def _moe_ffn(h, lp, s):
    E, k = s["experts"], s["top_k"]
    scores = jax.nn.sigmoid(h @ _f32(lp["router"]))                    # [T, E]
    choose = scores + _f32(lp["expert_bias"]) if s["use_bias"] else scores
    _, chosen = lax.top_k(choose, k)                                   # [T, k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    weight = jnp.where(picked, scores, 0.0)
    if s["norm_topk"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    weight = weight * s["scaling"]
    first, count = s["held"] or (0, E)
    inter = lp["w2"].shape[1]

    def expert(out, e):
        a = h @ _f32(lp["w13"][e])
        y = (jax.nn.silu(a[:, :inter]) * a[:, inter:]) @ _f32(lp["w2"][e])
        return out + weight[:, e, None] * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(first, first + count))
    return out


def _layer(x, mixer, mp, ffn, fp, s):
    x = x + mixer(_rmsnorm(x, _f32(mp["mixer_norm"]), s["eps"]), mp, s)
    return x + ffn(_rmsnorm(x, _f32(fp["mlp_norm"]), s["eps"]), fp)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _forward(params, tokens, last, frozen):
    s = dict(frozen)
    x = _f32(params["embed"][tokens])
    kinds, i = s["kinds"], 0
    while i < len(kinds):
        # a run of layers of one mixer and one feed-forward kind
        sparse = i >= s["dense"]
        run = 1
        while (i + run < len(kinds) and kinds[i + run] == kinds[i]
               and (i + run >= s["dense"]) == sparse):
            run += 1
        mixer, group = ((_attn_mixer, "attn_layers") if kinds[i] == "full_attention"
                        else (_conv_mixer, "conv_layers"))
        ffn, fgroup = ((lambda h, fp: _moe_ffn(h, fp, s), "moe_ffn") if sparse
                       else (_dense_ffn, "dense_ffn"))
        m0 = kinds[:i].count(kinds[i])
        f0 = i - s["dense"] if sparse else i
        at = lambda tree, j: jax.tree.map(lambda a: a[j], tree)  # noqa: E731
        x, _ = lax.scan(
            lambda x, j: (_layer(x, mixer, at(params[group], m0 + j), ffn,
                                 at(params[fgroup], f0 + j), s), None),
            x, jnp.arange(run))
        i += run
    h = _rmsnorm(x[last], _f32(params["final_norm"]), s["eps"])
    head = params["embed"].T
    V = head.shape[1]
    logits = jnp.concatenate([
        h @ _f32(head[:, b:b + VOCAB_BLOCK]) for b in range(0, V, VOCAB_BLOCK)
    ])
    return logits - jax.scipy.special.logsumexp(logits)


def next_token_logprobs(params, doc: dict, tokens, pad_to: int = 0, experts_held=None):
    """log p(next token | tokens) as a float32 [V] array. `pad_to` pads the
    sequence on the right (causally inert) so that growing sequences share one
    compile. `experts_held` (first, count) gives the reference the chip's
    share of every expert layer (default: all)."""
    n = len(tokens)
    ids = jnp.zeros((max(n, pad_to),), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    s = settings(doc)
    if experts_held is not None:
        s["held"] = tuple(experts_held)
    with jax.default_matmul_precision("highest"):
        return _forward(params, ids, n - 1, tuple(sorted(s.items())))
