"""Plain reference of the DeepseekV3ForCausalLM decoder (Moonlight, DeepSeek-V3).

Straightforward `jax.numpy` in float32 with matrix products at "highest"
precision: no kernels, no cache, no pages, no batching tricks, no absorbed
projections. It imports nothing of the program under test. It follows the
published modelling code (transformers `modeling_deepseek_v3.py`):

    h   = embed[ids]
    per layer:
      x = rmsnorm(h) ; MLA: q = x Wq            -> (heads, nope + rope)
                            kv = x Wkv_down     -> latent (kv_lora) | k_rope
                            k_nope, v = rmsnorm(latent) Wkv_up
                            rope on q_rope and the one shared k_rope head
                            causal softmax((q . k) / sqrt(nope + rope)) v, Wo
      h = h + attention
      x = rmsnorm(h) ; dense layer: Wdown(silu(x Wgate) * x Wup)
                       expert layer: scores = sigmoid(x Wrouter)
                                     top-k of (scores + selection bias)
                                     weights = scores[top] / sum * scaling
                                     sum_k weights_k expert_k(x) + shared(x)
      h = h + mlp
    logits = rmsnorm(h) Whead

Departures from the published code, each on purpose: rope rotates the two
halves of the rope head against each other ("half-split", the layout the
program's checkpoint adapter converts to) instead of adjacent pairs, which is
the same function under a fixed permutation of the rope columns of Wq and
Wkv_down; `n_group` = `topk_group` = 1 (Moonlight) so group-limited routing
is the identity and is not written out; every expert is computed for every
token and the unselected ones are weighted 0 (plain, and exact).

The reference asks for its own leaves: `leaf(path)` for one that has no layer
axis (the embedding, the final norm, the head), `layer(stack, l)` for one
layer's, so a model whose float32 copy does not fit the device can still be
followed, and the harness names no leaf.

The control (`control="int8"` or `"fp8"`) is this same reference with both
operands of every weight matrix product (projections, experts, shared experts,
head; not the router, which low-precision serving keeps in float32) rounded to
the lower precision first: one scale per row of the activations and per column
of the weights, as dynamic low-precision serving does it.
It exists to show that the comparison which decides `correct` fails for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def round_to(x, control: str, axis: int):
    """x with each slice along `axis` rounded to the values that int8
    (symmetric, 127 steps) or float8-e4m3 (scaled to its largest finite
    value, 448) can hold."""
    peak = jnp.max(jnp.abs(x), axis=axis, keepdims=True) + 1e-30
    if control == "int8":
        return jnp.round(x / peak * 127.0) * (peak / 127.0)
    if control == "fp8":
        y = (x / peak * 448.0).astype(jnp.float8_e4m3fn).astype(F32)
        return y * (peak / 448.0)
    raise ValueError(f"no control precision {control!r}")


def matmul(control: str | None):
    """`x @ w` for the reference (control None) or for the control."""
    if control is None:
        return jnp.matmul
    return lambda x, w: round_to(x, control, -1) @ round_to(w, control, -2)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (B, S, heads, d): rotate the first half of d against the second."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[..., None].astype(F32) * inv_freq      # (B, S, d/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, cfg, mm):
    B, S, _ = h.shape
    n = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = rmsnorm(h, w["input_norm/scale"], eps)
    q = mm(x, w["q_proj/kernel"]).reshape(B, S, n, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])
    kv = mm(x, w["kv_down_proj/kernel"])
    latent = rmsnorm(kv[..., :r], w["kv_norm/scale"], eps)
    k_rope = rope(kv[..., r:][:, :, None, :], pos, cfg["rope_theta"])
    kv_up = mm(latent, w["kv_up_proj/kernel"]).reshape(B, S, n, dn + dv)
    k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
    s = (jnp.einsum("bqnd,bknd->bnqk", q_nope, k_nope)
         + jnp.einsum("bqnd,bkd->bnqk", q_rope, k_rope[:, :, 0]))
    s = s * (dn + dr) ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    out = jnp.einsum("bnqk,bknd->bqnd", p, v).reshape(B, S, n * dv)
    return h + mm(out, w["o_proj/kernel"])


def gated_mlp(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def dense_layer(h, w, cfg, mm):
    h = attention(h, w, cfg, mm)
    x = rmsnorm(h, w["post_attn_norm/scale"], cfg["rms_norm_eps"])
    return h + gated_mlp(x, w["gate_proj/kernel"], w["up_proj/kernel"],
                         w["down_proj/kernel"], mm)


def route(x, w, cfg):
    """(T, E) combine weights: 0 for the experts a token does not use."""
    E, K = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["scoring_func"] == "sigmoid"
    scores = jax.nn.sigmoid(x @ w["moe/gate/weight"])
    select = scores + w.get("moe/gate/e_score_bias", 0.0)
    _, top = jax.lax.top_k(select, K)
    chosen = jnp.take_along_axis(scores, top, -1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    chosen = chosen * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros((x.shape[0], E), F32).at[rows, top].set(chosen)


def expert_layer(h, w, cfg, mm):
    h = attention(h, w, cfg, mm)
    B, S, H = h.shape
    x = rmsnorm(h, w["post_attn_norm/scale"], cfg["rms_norm_eps"])
    flat = x.reshape(B * S, H)
    combine = route(flat, w, cfg)

    def one_expert(acc, e):
        gate, up, down, weight = e
        return acc + weight[:, None] * gated_mlp(flat, gate, up, down, mm), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (w["moe/experts/gate_proj/kernel"], w["moe/experts/up_proj/kernel"],
         w["moe/experts/down_proj/kernel"], combine.T),
    )
    shared = gated_mlp(flat, w["moe/shared/gate_proj/kernel"],
                       w["moe/shared/up_proj/kernel"],
                       w["moe/shared/down_proj/kernel"], mm)
    return h + (routed + shared).reshape(B, S, H)


def stacks(cfg) -> list:
    """[(stack name, layer function, number of layers)] in order."""
    k = cfg["first_k_dense_replace"]
    out = [("dense_layers", dense_layer, k)] if k else []
    return out + [("moe_layers", expert_layer, cfg["num_hidden_layers"] - k)]


def hidden_states(cfg, ids, leaf, layer, control=None):
    """Final hidden states (B, S, H), before the last norm. `leaf(path)` makes
    a leaf that has no layer axis, by its path in the program's tree, as it is
    served; `layer(stack, l)` -> {leaf path: float32 array} makes one layer's.
    Each layer is one jitted call, so only one layer's float32 weights are
    alive at a time."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed/embedding").astype(F32), ids, axis=0)
        for stack, fn, n in stacks(cfg):
            step = jax.jit(lambda h, w, fn=fn: fn(h, w, cfg, matmul(control)))
            for l in range(n):
                h = step(h, layer(stack, l))
        return h


def logits_at(cfg, h_rows, leaf, control=None):
    """Float32 logits (N, V) of the chosen rows (N, H) of the hidden states."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(h_rows, leaf("final_norm/scale").astype(F32),
                    cfg["rms_norm_eps"])
        return jax.jit(matmul(control))(x, leaf("lm_head/kernel").astype(F32))
