"""Plain reference forward of the Llama family (Llama, Mistral, Qwen2).

Written from the published descriptions (the HF `config.json` keys and the
papers' equations), independent of the program: float32 `jax.numpy`, `highest`
matmul precision, no cache, no kernels, no batching. One sequence in, the
log-probabilities of the next token out.

  x_0 = E[tokens]
  per layer:  h = rmsnorm(x) ; q,k,v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
              q,k = rope(q), rope(k)       (half-rotation, as HF's rotate_half)
              a = softmax(q k^T / sqrt(D) + causal/window mask) v   (GQA: each
                  kv head serves num_heads / num_kv_heads query heads)
              x = x + a Wo ; x = x + (silu(h' Wg) * (h' Wu)) Wd, h' = rmsnorm(x)
  logits = rmsnorm(x) W_head  (or E^T when the embeddings are tied)

`sliding_window` w: query i attends keys j with i - w < j <= i (Mistral's
definition: the window counts the current token). Qwen2 windows nothing unless
`use_sliding_window` is true.

Departures from a textbook forward, for memory only (the mathematics is
unchanged): the layers are walked with `lax.scan` over the stacked bf16 weights,
each layer cast to float32 as it is used; attention runs in query blocks; the
head is taken at one position, in vocabulary blocks.

The parameter tree is the program's (`models/llama.init_params` leaf names):
embed [V,H]; layers.{attn_norm,mlp_norm}[L,H]; layers.{wq,wk,wv,wo,w_gate,w_up,
w_down}[L,in,out]; optional layers.{bq,bk,bv}; final_norm [H]; lm_head [H,V].
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
    arch = (doc.get("architectures") or [""])[0]
    window = doc.get("sliding_window")
    if arch.startswith("Qwen2") and not doc.get("use_sliding_window", False):
        window = None
    return {
        "heads": heads,
        "kv_heads": doc.get("num_key_value_heads", heads),
        "head_dim": doc.get("head_dim") or doc["hidden_size"] // heads,
        "eps": doc.get("rms_norm_eps", 1e-5),
        "theta": doc.get("rope_theta", 10000.0),
        "window": window,
        "bias": doc.get("attention_bias", arch.startswith("Qwen2")),
        "tied": doc.get("tie_word_embeddings", False),
    }


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    # x [T, heads, D]; rotate the pairs (x[:D/2], x[D/2:]) as HF's rotate_half
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


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
        ok = kpos[None, :] <= qpos[:, None]
        if s["window"]:
            ok &= kpos[None, :] > qpos[:, None] - s["window"]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(D))
        scores = jnp.where(ok[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs, axis=0).reshape(T, NH * D)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _forward(params, tokens, last, frozen):
    s = dict(frozen)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = f32(params["embed"][tokens])

    def layer(x, lp):
        h = _rmsnorm(x, f32(lp["attn_norm"]), s["eps"])
        q, k, v = h @ f32(lp["wq"]), h @ f32(lp["wk"]), h @ f32(lp["wv"])
        if s["bias"]:
            q, k, v = q + f32(lp["bq"]), k + f32(lp["bk"]), v + f32(lp["bv"])
        q = _rope(q.reshape(T, s["heads"], s["head_dim"]), positions, s["theta"])
        k = _rope(k.reshape(T, s["kv_heads"], s["head_dim"]), positions, s["theta"])
        v = v.reshape(T, s["kv_heads"], s["head_dim"])
        x = x + _attention(q, k, v, s) @ f32(lp["wo"])
        h = _rmsnorm(x, f32(lp["mlp_norm"]), s["eps"])
        x = x + (jax.nn.silu(h @ f32(lp["w_gate"])) * (h @ f32(lp["w_up"]))) @ f32(lp["w_down"])
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    h = _rmsnorm(x[last], f32(params["final_norm"]), s["eps"])
    head = params["embed"].T if s["tied"] else params["lm_head"]
    V = head.shape[1]
    logits = jnp.concatenate([
        h @ f32(head[:, b:b + VOCAB_BLOCK]) for b in range(0, V, VOCAB_BLOCK)
    ])
    return logits - jax.scipy.special.logsumexp(logits)


def next_token_logprobs(params, doc: dict, tokens, pad_to: int = 0):
    """log p(next token | tokens) as a float32 [V] array. `pad_to` pads the
    sequence (causally inert) so that growing sequences share one compile."""
    n = len(tokens)
    ids = jnp.zeros((max(n, pad_to),), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    frozen = tuple(sorted(settings(doc).items()))
    with jax.default_matmul_precision("highest"):
        return _forward(params, ids, n - 1, frozen)
