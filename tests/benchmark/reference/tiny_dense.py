"""Plain reference of a Mistral-shaped dense decoder with qkv biases (the
program's Qwen2ForCausalLM), for the tests' `tiny_dense` configuration. It is the proof that a second architecture enters the harness
as data: this file, its counts, a configuration, limits and manifest entries,
and no edit to a file under benchmark/. Float32, "highest" matrix products; it
imports nothing of the program (the helpers are the benchmark's own).

    h = embed[ids]
    per layer:
      x = rmsnorm(h); q, k, v = x Wq + bq, x Wk + bk, x Wv + bv
      rope (half-split) on q and k; each key/value head serves
      heads / kv_heads query heads; causal softmax(q . k / sqrt(d)) v, Wo
      h = h + attention
      h = h + Wdown(silu(rmsnorm(h) Wgate) * rmsnorm(h) Wup)
    logits = rmsnorm(h) Whead

The control rounds both operands of every weight product, as
benchmark/reference/deepseek_v3.py does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import F32, matmul, rmsnorm, rope


def layer(h, w, cfg, mm):
    B, S, H = h.shape
    n, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg.get("head_dim") or H // n, cfg["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = rmsnorm(h, w["input_norm/scale"], eps)
    q, k, v = ((mm(x, w[f"{p}_proj/kernel"]) + w[f"{p}_proj/bias"])
               .reshape(B, S, heads, d)
               for p, heads in (("q", n), ("k", nkv), ("v", nkv)))
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k, v = jnp.repeat(k, n // nkv, axis=2), jnp.repeat(v, n // nkv, axis=2)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) * d ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    out = jnp.einsum("bnqk,bknd->bqnd", p, v).reshape(B, S, n * d)
    h = h + mm(out, w["o_proj/kernel"])
    x = rmsnorm(h, w["post_attn_norm/scale"], eps)
    gated = jax.nn.silu(mm(x, w["gate_proj/kernel"])) * mm(x, w["up_proj/kernel"])
    return h + mm(gated, w["down_proj/kernel"])


def stacks(cfg) -> list:
    """[(stack name, layer function, number of layers)] in order."""
    return [("layers", layer, cfg["num_hidden_layers"])]


def hidden_states(cfg, ids, leaf, layer, control=None):
    """Final hidden states (B, S, H), before the last norm; `leaf(path)` and
    `layer(stack, l)` as benchmark/reference/deepseek_v3.py describes them."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed/embedding").astype(F32), ids, axis=0)
        for stack, fn, n in stacks(cfg):
            step = jax.jit(lambda h, w, fn=fn: fn(h, w, cfg, matmul(control)))
            for l in range(n):
                h = step(h, layer(stack, l))
        return h


def logits_at(cfg, h_rows, leaf, control=None):
    """Float32 logits (N, V) of the chosen rows (N, H)."""
    assert not cfg["tie_word_embeddings"], "a tied head is not written out"
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(h_rows, leaf("final_norm/scale").astype(F32),
                    cfg["rms_norm_eps"])
        return jax.jit(matmul(control))(x, leaf("lm_head/kernel").astype(F32))
