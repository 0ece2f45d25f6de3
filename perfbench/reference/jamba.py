"""Plain reference forward of Jamba (AI21 Jamba / Jamba2, `model_type: jamba`,
`num_experts == 1`).

Written from the published `config.json` keys and the equations of the Mamba
and Jamba papers, independent of the program: float32 `jax.numpy`, `highest`
matmul precision, no cache, no state pool, no kernels, no batching. One
sequence in, the log-probabilities of the next token out.

  x_0 = E[tokens]
  layer i is an attention layer iff i % attn_layer_period == attn_layer_offset,
  else a state-space (Mamba-1) layer; every layer:
      x = x + mixer(rmsnorm(x)) ; x = x + (silu(h Wg) * (h Wu)) Wd, h = rmsnorm(x)
  attention mixer:  q, k, v = h Wq, h Wk, h Wv (no bias, NO positional
      embedding); a = softmax(q k^T / sqrt(D) + causal mask) v (each kv head
      serves num_heads / num_kv_heads query heads); a Wo
  state-space mixer (Di = mamba_expand * hidden, N = mamba_d_state, R =
      mamba_dt_rank, K = mamba_d_conv):
      [u, z] = h W_in
      u_t = silu(b_conv + sum_{j<K} w_j * u_{t-K+1+j})        (causal, depthwise)
      [dt, B, C] = u W_x ; dt, B, C = rmsnorm(dt), rmsnorm(B), rmsnorm(C)
      delta = softplus(dt W_dt + b_dt) ; A = -exp(A_log)
      h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * u_t) (x) B_t ; h_{-1} = 0
      y_t = h_t C_t + D * u_t ; out = (y * silu(z)) W_out
  logits = rmsnorm(x) E^T                                     (tied head)

Departures from a textbook forward, for memory and compile time only (the
mathematics is unchanged): each run of consecutive state-space layers is walked
with `lax.scan` over its layer indices into the stacked bf16 weights (no slice
of the stack is copied), each layer cast to float32 as it is used; time is
walked with `lax.scan`, one step at a time; attention runs in query blocks;
the head is taken at one position, in vocabulary blocks.

The parameter tree is the program's (`models/jamba.init_params` leaf names):
embed [V,H]; layers.* the state-space layers stacked [Ls,...] in layer order
(mixer_norm, in_proj [H,2Di], conv_w [K,Di], conv_b, x_proj [Di,R+2N], dt_norm,
b_norm, c_norm, dt_proj [R,Di], dt_bias, a_log [Di,N], d_skip, out_proj [Di,H],
mlp_norm, w_gate, w_up, w_down); attn_layers.* the attention layers stacked
[La,...] (mixer_norm, wq, wk, wv, wo, mlp_norm, w_gate, w_up, w_down);
final_norm [H].
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
        "layers": doc["num_hidden_layers"],
        "period": doc["attn_layer_period"],
        "offset": doc["attn_layer_offset"],
        "heads": heads,
        "kv_heads": doc.get("num_key_value_heads", heads),
        "head_dim": doc.get("head_dim") or doc["hidden_size"] // heads,
        "eps": doc.get("rms_norm_eps", 1e-6),
        "state": doc.get("mamba_d_state", 16),
        "conv": doc.get("mamba_d_conv", 4),
        "rank": doc["mamba_dt_rank"],
    }


def _f32(a):
    return a.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def _mlp(x, lp, eps):
    h = _rmsnorm(x, _f32(lp["mlp_norm"]), eps)
    return x + (jax.nn.silu(h @ _f32(lp["w_gate"])) * (h @ _f32(lp["w_up"]))) @ _f32(lp["w_down"])


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


def _attn_layer(x, lp, s):
    T = x.shape[0]
    h = _rmsnorm(x, _f32(lp["mixer_norm"]), s["eps"])
    q = (h @ _f32(lp["wq"])).reshape(T, s["heads"], s["head_dim"])
    k = (h @ _f32(lp["wk"])).reshape(T, s["kv_heads"], s["head_dim"])
    v = (h @ _f32(lp["wv"])).reshape(T, s["kv_heads"], s["head_dim"])
    x = x + _attention(q, k, v, s) @ _f32(lp["wo"])
    return _mlp(x, lp, s["eps"])


def _ssm_layer(x, lp, s, state_dtype):
    T = x.shape[0]
    K, N, R = s["conv"], s["state"], s["rank"]
    h = _rmsnorm(x, _f32(lp["mixer_norm"]), s["eps"])
    u, z = jnp.split(h @ _f32(lp["in_proj"]), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), jnp.float32), u])
    w = _f32(lp["conv_w"])
    u = jax.nn.silu(_f32(lp["conv_b"]) + sum(w[j] * padded[j:j + T] for j in range(K)))
    dt, b_mat, c_mat = jnp.split(u @ _f32(lp["x_proj"]), [R, R + N], axis=-1)
    dt = _rmsnorm(dt, _f32(lp["dt_norm"]), s["eps"])
    b_mat = _rmsnorm(b_mat, _f32(lp["b_norm"]), s["eps"])
    c_mat = _rmsnorm(c_mat, _f32(lp["c_norm"]), s["eps"])
    delta = jax.nn.softplus(dt @ _f32(lp["dt_proj"]) + _f32(lp["dt_bias"]))
    a = -jnp.exp(_f32(lp["a_log"]))  # [Di, N]

    def step(state, xs):
        d_t, u_t, b_t, c_t = xs
        state = jnp.exp(d_t[:, None] * a) * state + (d_t * u_t)[:, None] * b_t[None, :]
        return state.astype(state_dtype), state @ c_t

    _, y = lax.scan(step, jnp.zeros(a.shape, state_dtype), (delta, u, b_mat, c_mat))
    y = y + _f32(lp["d_skip"]) * u
    x = x + (y * jax.nn.silu(z)) @ _f32(lp["out_proj"])
    return _mlp(x, lp, s["eps"])


@functools.partial(jax.jit, static_argnames=("frozen", "state_dtype"))
def _forward(params, tokens, last, frozen, state_dtype):
    s = dict(frozen)
    x = _f32(params["embed"][tokens])
    kinds = ["attn" if i % s["period"] == s["offset"] else "ssm" for i in range(s["layers"])]
    n_ssm = n_attn = i = 0
    while i < len(kinds):
        if kinds[i] == "attn":
            x = _attn_layer(x, jax.tree.map(lambda a: a[n_attn], params["attn_layers"]), s)
            n_attn, i = n_attn + 1, i + 1
            continue
        run = 0
        while i + run < len(kinds) and kinds[i + run] == "ssm":
            run += 1
        x, _ = lax.scan(
            lambda x, l: (_ssm_layer(
                x, jax.tree.map(lambda a: a[l], params["layers"]), s, state_dtype), None),
            x, jnp.arange(n_ssm, n_ssm + run))
        n_ssm, i = n_ssm + run, i + run
    h = _rmsnorm(x[last], _f32(params["final_norm"]), s["eps"])
    head = params["embed"].T
    V = head.shape[1]
    logits = jnp.concatenate([
        h @ _f32(head[:, b:b + VOCAB_BLOCK]) for b in range(0, V, VOCAB_BLOCK)
    ])
    return logits - jax.scipy.special.logsumexp(logits)


def next_token_logprobs(params, doc: dict, tokens, pad_to: int = 0, state_dtype=jnp.float32):
    """log p(next token | tokens) as a float32 [V] array. `pad_to` pads the
    sequence on the right (causally inert) so that growing sequences share one
    compile. `state_dtype` is float32, as the configuration states; the
    low-precision control (scripts/jamba_lowprec_control.py) alone passes
    bfloat16: the recurrent state is then rounded between two steps."""
    n = len(tokens)
    ids = jnp.zeros((max(n, pad_to),), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    frozen = tuple(sorted(settings(doc).items()))
    with jax.default_matmul_precision("highest"):
        return _forward(params, ids, n - 1, frozen, jnp.dtype(state_dtype))
