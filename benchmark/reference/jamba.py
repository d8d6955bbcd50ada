"""Plain reference of the JambaForCausalLM decoder, dense sizes (AI21 Jamba2-3B,
Jamba Reasoning 3B): Mamba-1 state-space layers with an attention layer every
`attn_layer_period`-th, a gated MLP in every layer, no positional encoding.

Straightforward `jax.numpy` in float32 with matrix products at "highest"
precision: no kernels, no cache, no pages, no carried state: one full forward
over each sequence from a zero state. It imports nothing of the program under
test. It follows the family's published modelling code (`modeling_jamba.py`
in transformers) and the Mamba paper (Gu & Dao, section 3):

    h = embed[ids]
    for layer i = 0 .. L - 1:
      x = rmsnorm(h; input_layernorm)
      i % attn_layer_period == attn_layer_offset:
        h += Wo softmax_causal((x Wq) (x Wk)^T / sqrt(head_dim)) (x Wv)
             20 query heads over ONE key/value head (grouped), no bias, and
             NO rotary embedding: the model has no positional encoding
      otherwise (the Mamba-1 mixer; d_inner C = expand * hidden, N = d_state,
      K = d_conv, R = dt_rank):
        [u, z] = x Win                                  (.., 2C) split in two
        u      = silu(conv(u) + b_conv)                 depthwise, causal, over
                                                        the last K positions
        [dt, B, C_] = u Wx                              split R / N / N
        dt, B, C_ = rmsnorm(dt), rmsnorm(B), rmsnorm(C_)   Jamba's inner norms
        delta  = softplus(dt Wdt + dt_bias)             (.., C)
        A      = -exp(A_log)                            (N, C)
        s_t    = exp(delta_t A) * s_{t-1} + (delta_t u_t) B_t     s_-1 = 0
        y_t    = sum_n s_t[n] C_t[n] + D u_t
        h     += (y * silu(z)) Wout
      h += Wdown(silu(x Wgate) * x Wup),  x = rmsnorm(h; pre_ff_layernorm)
    logits = rmsnorm(h; final_layernorm) Whead

RMSNorm is the plain scale (not zero-centred), eps `rms_norm_eps`. The head
is the transposed embedding where `tie_word_embeddings` is true (as the 3B
sizes are published) and a matrix of its own where it is false.

Layouts are the program's tree's, because the leaves are asked for by their
paths in it: `conv/kernel` (K, C) with row K-1 on the current position
(conv1d.weight (C, 1, K) transposed), `A_log` (N, C), every Linear (in, out);
the mixer's output projection is `o_proj`. The state s is (B, N, C) here.

Departures from the published code, each on purpose: the recurrence is a
`lax.scan` over positions (the published slow path loops in Python; the fast
path is a CUDA kernel); attention is computed a block of queries at a time,
which changes no number; `num_experts` > 1 (the larger sizes' expert layers)
is refused.

Which subtrees carry a layer axis: `layers` (what every layer has: the two
norms and the MLP), `attn_layers` and `mamba_layers` (the operators, entry j
the j-th layer of that kind). `LEAF_RULES` draws the three leaves that only
this architecture has so that they lie where the family initialises them:
`A_log` about log(1..16), `D` about 1, `dt_bias` so that softplus gives a
delta of about 0.003 - 0.04 (the family: log-uniform over 0.001 - 0.1). A
bias drawn near 0 would give delta 0.7 and a state that forgets in two tokens.

The control (`control="int8"` or `"fp8"`) is this same reference with both
operands of every weight matrix product rounded to the lower precision first
(one scale per row of the activations and per column of the weights). It
exists to show that the comparison which decides `correct` fails for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: {last path component: (mean, std)} of the leaves only this architecture has
LEAF_RULES = {
    "A_log": (1.5, 0.7),
    "D": (1.0, 0.1),
    "dt_bias": (-4.6, 0.7),
}

QUERY_BLOCK = 512


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


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg) -> list:
    """Per layer "attention" or "mamba"."""
    if int(cfg.get("num_experts", 1)) != 1:
        raise NotImplementedError("num_experts > 1: the expert layers")
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def dt_rank(cfg) -> int:
    rank = cfg.get("mamba_dt_rank", "auto")
    return -(-cfg["hidden_size"] // 16) if rank in (None, "auto") else int(rank)


def attention(x, w, cfg, mm):
    """The attention branch on the normed state x (B, S, H): no rotary."""
    B, S, _ = x.shape
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = mm(x, w["q_proj/kernel"]).reshape(B, S, nkv, n // nkv, d)
    k = mm(x, w["k_proj/kernel"]).reshape(B, S, nkv, d)
    v = mm(x, w["v_proj/kernel"]).reshape(B, S, nkv, d)
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q[:, lo:hi], k[:, :hi]) * d ** -0.5
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        out.append(jnp.einsum("bhgqk,bkhd->bqhgd", p, v[:, :hi]))
    out = jnp.concatenate(out, 1).reshape(B, S, n * d)
    return mm(out, w["o_proj/kernel"])


def mamba(x, w, cfg, mm):
    """The Mamba-1 mixer branch on the normed state x (B, S, H)."""
    B, S, _ = x.shape
    N, K, R = cfg["mamba_d_state"], cfg["mamba_d_conv"], dt_rank(cfg)
    eps = cfg["rms_norm_eps"]
    u, z = jnp.split(mm(x, w["in_proj/kernel"]), 2, axis=-1)
    taps = w["conv/kernel"]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = sum(padded[:, k:k + S] * taps[k] for k in range(K)) + w["conv/bias"]
    u = jax.nn.silu(u)
    dt, b, c = jnp.split(mm(u, w["x_proj/kernel"]), [R, R + N], axis=-1)
    dt = rmsnorm(dt, w["dt_norm/scale"], eps)
    b = rmsnorm(b, w["b_norm/scale"], eps)
    c = rmsnorm(c, w["c_norm/scale"], eps)
    delta = jax.nn.softplus(mm(dt, w["dt_proj/kernel"]) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                                   # (N, C)

    def position(s, xs):
        delta_t, u_t, b_t, c_t = xs                            # (B, C) / (B, N)
        s = (jnp.exp(delta_t[:, None, :] * a) * s
             + b_t[:, :, None] * (delta_t * u_t)[:, None, :])
        return s, jnp.einsum("bnc,bn->bc", s, c_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (delta, u, b, c))
    _, y = jax.lax.scan(position, jnp.zeros((B, N, u.shape[-1]), F32), xs)
    y = jnp.moveaxis(y, 0, 1) + w["D"] * u
    return mm(y * jax.nn.silu(z), w["o_proj/kernel"])


def decoder_layer(h, w, cfg, mm, kind: str):
    """One layer; `w` holds the layer's own leaves and its operator's."""
    eps = cfg["rms_norm_eps"]
    x = rmsnorm(h, w["input_norm/scale"], eps)
    h = h + (attention if kind == "attention" else mamba)(x, w, cfg, mm)
    x = rmsnorm(h, w["post_attn_norm/scale"], eps)
    return h + mm(jax.nn.silu(mm(x, w["gate_proj/kernel"])) * mm(x, w["up_proj/kernel"]),
                  w["down_proj/kernel"])


def stacks(cfg) -> list:
    """[(stack name, layer function, number of entries)]: the subtrees whose
    leaves carry a layer axis. The layers are not walked stack by stack:
    `hidden_states` takes layer i's leaves from `layers` and its operator's
    from the stack of its kind."""
    kinds = layer_kinds(cfg)
    out = [("layers", decoder_layer, len(kinds))]
    for kind, name in (("attention", "attn_layers"), ("mamba", "mamba_layers")):
        if kind in kinds:
            out.append((name, decoder_layer, kinds.count(kind)))
    return out


def hidden_states(cfg, ids, leaf, layer, control=None):
    """What the head reads (B, S, H): the state after the final norm.
    `leaf(path)` makes a leaf that has no layer axis, as it is served;
    `layer(stack, l)` -> {leaf path: float32 array} one entry of a stack.
    Each layer is one jitted call (one program per kind)."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed/embedding").astype(F32), ids, axis=0)
        step = jax.jit(
            lambda h, w, kind: decoder_layer(h, w, cfg, matmul(control), kind),
            static_argnums=2)
        seen = {"attention": 0, "mamba": 0}
        names = {"attention": "attn_layers", "mamba": "mamba_layers"}
        for i, kind in enumerate(layer_kinds(cfg)):
            w = dict(layer("layers", i))
            w.update(layer(names[kind], seen[kind]))
            seen[kind] += 1
            h = step(h, w, kind)
        return rmsnorm(h, leaf("final_norm/scale").astype(F32), cfg["rms_norm_eps"])


def logits_at(cfg, h_rows, leaf, control=None):
    """Float32 logits (N, V) of the chosen rows (N, H) of `hidden_states`."""
    with jax.default_matmul_precision("highest"):
        if cfg.get("tie_word_embeddings"):
            head = leaf("embed/embedding").astype(F32).T
        else:
            head = leaf("lm_head/kernel").astype(F32)
        return jax.jit(matmul(control))(h_rows, head)
