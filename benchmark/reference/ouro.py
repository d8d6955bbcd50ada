"""Plain reference of the OuroForCausalLM decoder (ByteDance Ouro 1.4B / 2.6B,
"Scaling Latent Reasoning via Looped Language Models"): a dense decoder
whose whole layer stack runs `total_ut_steps` times over every token.

Straightforward `jax.numpy` in float32 with matrix products at "highest"
precision: no kernels, no cache, no pages, no batching tricks. It imports
nothing of the program under test. It follows the family's published
modelling code (`modeling_ouro.py` beside the checkpoint) and the paper:

    h = embed[ids]
    for pass t = 0 .. total_ut_steps - 1:
      for layer l = 0 .. L - 1 (the SAME weights in every pass):
        a = h + rmsnorm_2(MHA(rmsnorm_1(h)))      input_layernorm, input_layernorm_2
            MHA: q, k, v = x Wq, x Wk, x Wv -> (heads, head_dim), no bias,
                 no qk-norm; rope (theta) on the whole head of q and k;
                 causal softmax((q . k) / sqrt(head_dim)) v, Wo
        h = a + rmsnorm_4(Wdown(silu(x Wgate) * x Wup)),  x = rmsnorm_3(a)
                                                   post_attention_layernorm{,_2}
      h = rmsnorm_f(h)              the final norm after EVERY pass; the normed
                                    state is the next pass's input
      g_t = sigmoid(w_g . h + b_g)  the exit gate, one Linear hidden -> 1 with
                                    bias, shared by the passes
    logits = h Whead                of the last pass (see below)

Keys and values of pass t, layer l are a cache entry of their own in the
published code (index t * L + l): a token of pass t attends to the earlier
tokens' keys of the SAME pass and layer. A full forward over the sequence
does exactly that and needs no cache.

Exit: p_t = g_t * prod_{j<t} (1 - g_j), the last pass takes what is left; a
token leaves at the first pass where the cumulated p reaches
`early_exit_threshold`. With the published threshold of 1 that is the last
pass for every token (a sigmoid is below 1), so the logits are the last
pass's; `exit_distribution` gives the p_t for the tests.

Departures from the published code, each on purpose: rope rotates the two
halves of the head against each other ("half-split", as the published code
does too: no permutation is needed here); keys and values are not repeated
over groups (`num_key_value_heads` equals `num_attention_heads` in both
published sizes; grouping is written out so a grouped toy still runs);
`early_exit_threshold` below 1 (rows leaving at different passes) is refused.

The reference asks for its own leaves: `leaf(path)` for one that has no layer
axis (the embedding, the final norm, the head, the exit gate), `layer(stack,
l)` for one layer's, made anew in every pass so that only one layer's float32
weights are alive at a time.

The control (`control="int8"` or `"fp8"`) is this same reference with both
operands of every weight matrix product (projections, MLP, head; not the
gate, one row) rounded to the lower precision first: one scale per row of
the activations and per column of the weights, as dynamic low-precision
serving does it. It exists to show that the comparison which decides
`correct` fails for it.
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


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def attention(h, w, cfg, mm):
    B, S, _ = h.shape
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    eps = cfg["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = rmsnorm(h, w["input_norm/scale"], eps)
    q = rope(mm(x, w["q_proj/kernel"]).reshape(B, S, n, d), pos, cfg["rope_theta"])
    k = rope(mm(x, w["k_proj/kernel"]).reshape(B, S, nkv, d), pos, cfg["rope_theta"])
    v = mm(x, w["v_proj/kernel"]).reshape(B, S, nkv, d)
    k, v = (jnp.repeat(a, n // nkv, axis=2) for a in (k, v))
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) * d ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    out = jnp.einsum("bnqk,bknd->bqnd", p, v).reshape(B, S, n * d)
    return h + rmsnorm(mm(out, w["o_proj/kernel"]),
                       w["post_attn_out_norm/scale"], eps)


def looped_layer(h, w, cfg, mm):
    eps = cfg["rms_norm_eps"]
    a = attention(h, w, cfg, mm)
    x = rmsnorm(a, w["post_attn_norm/scale"], eps)
    mlp = mm(jax.nn.silu(mm(x, w["gate_proj/kernel"])) * mm(x, w["up_proj/kernel"]),
             w["down_proj/kernel"])
    return a + rmsnorm(mlp, w["post_mlp_norm/scale"], eps)


def stacks(cfg) -> list:
    """[(stack name, layer function, number of layers)] in order: ONE stack,
    however many times it is walked."""
    return [("layers", looped_layer, cfg["num_hidden_layers"])]


def passes(cfg) -> int:
    if float(cfg.get("early_exit_threshold", 1)) < 1:
        raise NotImplementedError("early_exit_threshold < 1: adaptive exit")
    return int(cfg["total_ut_steps"])


def pass_states(cfg, ids, leaf, layer, control=None):
    """The normed state (B, S, H) after each pass, a list of `passes(cfg)`.
    `leaf(path)` makes a leaf that has no layer axis, by its path in the
    program's tree, as it is served; `layer(stack, l)` -> {leaf path: float32
    array} makes one layer's. Each layer is one jitted call."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed/embedding").astype(F32), ids, axis=0)
        final = leaf("final_norm/scale").astype(F32)
        (stack, fn, n), = stacks(cfg)
        step = jax.jit(lambda h, w: fn(h, w, cfg, matmul(control)))
        out = []
        for _ in range(passes(cfg)):
            for l in range(n):
                h = step(h, layer(stack, l))
            h = rmsnorm(h, final, cfg["rms_norm_eps"])
            out.append(h)
        return out


def hidden_states(cfg, ids, leaf, layer, control=None):
    """What the head reads (B, S, H): the last pass's normed state."""
    return pass_states(cfg, ids, leaf, layer, control)[-1]


def logits_at(cfg, h_rows, leaf, control=None):
    """Float32 logits (N, V) of the chosen rows (N, H) of `hidden_states`
    (normed already: the final norm belongs to every pass)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(matmul(control))(
            h_rows, leaf("lm_head/kernel").astype(F32))


def gate_probabilities(cfg, states, leaf):
    """g_t (passes, B, S): the exit gate on each pass's normed state."""
    with jax.default_matmul_precision("highest"):
        w = leaf("exit_gate/kernel").astype(F32)
        b = leaf("exit_gate/bias").astype(F32)
        return jnp.stack([jax.nn.sigmoid((h @ w)[..., 0] + b[0]) for h in states])


def exit_distribution(gates):
    """p_t = g_t * prod_{j<t} (1 - g_j); the last pass takes what is left."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = gates * before
    return p.at[-1].set(before[-1])
