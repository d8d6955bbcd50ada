"""Plain reference of the Phi4FlashForCausalLM decoder (Phi-4-mini-flash-
reasoning: SambaY, the decoder-hybrid-decoder of arXiv:2507.06607).

Straightforward `jax.numpy` in float32 with matrix products at "highest"
precision: no kernels, no cache, no pages, no ring, no carried state, no row
skipped. Every one of the L layers runs over every position of each
sequence. It imports nothing of the program under test. The layer equations
(the configuration's own keys; `assumed` in its file lists what the config
does not carry):

    h = embed[ids]
    per layer i (L = num_hidden_layers, k = mb_per_layer):
      x = layernorm(h; input_norm)                      weight and bias, eps
      i < L/2 + 2 and i % k == 0: the Mamba-1 mixer (d_inner C = 2 x hidden,
          N = 16 states, K = 4 taps, R = ceil(hidden / 16)):
          [u, z] = x Win;  u = silu(conv_K(u) + b_conv)  depthwise, causal
          [r, B, C_] = u Wx;  delta = softplus(r Wdt + dt_bias);  A = -exp(A_log)
          s_t = exp(delta_t A) s_{t-1} + delta_t u_t B_t,  y_t = s_t C_t + D u_t
          h += (y * silu(z)) Wout              (no norm on r, B or C)
          layer L/2 keeps m = y, before the gate: the MEMORY
      i < L/2, otherwise: differential attention over a window of
          `sliding_window` keys; i = L/2 + 1: over every key before the row,
          and its keys and values are what the cross layers read
      i >= L/2 + 2 and i % k == 0: the GMU, h += (silu(x W1) * m) W2
      i >= L/2 + 2, otherwise: cross-attention, queries alone (q = x Wq + bq)
          against layer L/2 + 1's keys and values, every key up to the row
      x = layernorm(h; post_attn_norm);  h += Wdown(silu(x Wgate) * x Wup)
    logits = layernorm(h; final_norm) Whead

Differential attention (q, k, v = x Wq + bq, x Wk + bk, x Wv + bv; n query
heads over m key/value heads of d, no rotary embedding), written as
DiffLlama writes it (transformers' modeling_diffllama.py): each query head
i's softmax map A_i = softmax(q_i k_{i//(n/m)}^T / sqrt(d) + mask) weighs the
value heads repeated over the query heads, their two halves of heads side by
side (2d wide) and that repeated twice; heads i and i + n/2 are a pair,

    o_i = (1 - l0) rmsnorm(A_i V_i - lambda A_{i+n/2} V_{i+n/2}; sub_norm)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0,  l0 = 0.8 - 0.6 exp(-0.3 i)

with the sub-norm's learned scale and eps layer_norm_eps; the layer's output
is concat(o) Wo + bo. Attention runs a block of query rows at a time
(`lax.map` over sequences and blocks), which changes no number.

The reference asks for its own leaves, by their paths in the program's tree:
`leaf(path)` for one without a layer axis, `layer(stack, l)` for one layer's.
Every layer's norms and MLP lie in `layers`; its operator in its kind's
stack, entry j the j-th layer of that kind: `mamba_layers`,
`window_attn_layers`, `attn_layers`, `gmu_layers`, `cross_attn_layers`. The
control (`control="int8"` or `"fp8"`) rounds both operands of every weight
matrix product to the lower precision first. It exists to show that
`correct` fails for it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows of one block of attention scores, tokens of one block of an MLP
ROWS = 256

#: how the benchmark draws the leaves only this architecture has: A_log,
#: D and dt_bias as Jamba's reference draws them (a state that forgets over
#: tens of tokens, not two); the four lambda vectors N(0, 0.1), as the
#: family initialises them
LEAF_RULES = {
    "A_log": (1.5, 0.7),
    "D": (1.0, 0.1),
    "dt_bias": (-4.6, 0.7),
    "lambda_q1": (0.0, 0.1),
    "lambda_k1": (0.0, 0.1),
    "lambda_q2": (0.0, 0.1),
    "lambda_k2": (0.0, 0.1),
}

STACKS = ("mamba_layers", "window_attn_layers", "attn_layers", "gmu_layers",
          "cross_attn_layers")


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


def layernorm(x, w, name, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w[f"{name}/scale"] + w[f"{name}/bias"]


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def eps_of(cfg) -> float:
    return float(cfg.get("layer_norm_eps", 1e-5))


def layer_kinds(cfg) -> list:
    """Per layer its operator's stack, as the family's code lays them out."""
    L, k = int(cfg["num_hidden_layers"]), int(cfg.get("mb_per_layer", 2))
    out = []
    for i in range(L):
        if i < L // 2 + 2:
            out.append("mamba_layers" if i % k == 0 else
                       "attn_layers" if i == L // 2 + 1 else "window_attn_layers")
        else:
            out.append("gmu_layers" if i % k == 0 else "cross_attn_layers")
    return out


def blocks_of(n: int) -> int:
    return ROWS if n % ROWS == 0 else n


def by_blocks(fn, x):
    """`fn` over blocks of x's rows (S, width) -> (S, width out)."""
    S = x.shape[0]
    rows = blocks_of(S)
    out = jax.lax.map(fn, x.reshape(S // rows, rows, x.shape[-1]))
    return out.reshape(S, -1)


def mamba(h, w, cfg, mm):
    """One sequence, h (S, H): (h after the mixer, m = y before the gate)."""
    S = h.shape[0]
    N, K = int(cfg.get("mamba_d_state", 16)), int(cfg.get("mamba_d_conv", 4))
    R = -(-int(cfg["hidden_size"]) // 16)
    x = layernorm(h, w, "input_norm", eps_of(cfg))
    u, z = jnp.split(mm(x, w["in_proj/kernel"]), 2, axis=-1)
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = sum(padded[j:j + S] * w["conv/kernel"][j] for j in range(K)) + w["conv/bias"]
    u = jax.nn.silu(u)
    r, b, c = jnp.split(mm(u, w["x_proj/kernel"]), [R, R + N], axis=-1)
    delta = jax.nn.softplus(mm(r, w["dt_proj/kernel"]) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                                   # (N, C)

    def position(s, xs):
        delta_t, u_t, b_t, c_t = xs
        s = jnp.exp(delta_t[None, :] * a) * s + b_t[:, None] * (delta_t * u_t)[None, :]
        return s, c_t @ s

    _, y = jax.lax.scan(position, jnp.zeros((N, u.shape[-1]), F32), (delta, u, b, c))
    y = y + w["D"] * u
    return h + mm(y * jax.nn.silu(z), w["o_proj/kernel"]), y


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def differential(q, k, v, w, cfg, l0, window):
    """q (S, n, d) against k (S, m, d), v (S, m, d) of one sequence, the
    pairs combined: (S, n * d)."""
    S, n, d = q.shape
    m = k.shape[1]
    eps = eps_of(cfg)
    # DiffLlama's values: repeated over the query heads, the two halves of
    # heads side by side, and that twice
    vr = jnp.repeat(v, n // m, axis=1)                         # (S, n, d)
    vv = jnp.concatenate([vr[:, : n // 2], vr[:, n // 2:]], -1)
    vv = jnp.concatenate([vv, vv], 1)                          # (S, n, 2d)
    kr = jnp.repeat(k, n // m, axis=1)                         # (S, n, d)
    pos = jnp.arange(S)
    rows = blocks_of(S)

    def block(args):
        qb, pb = args
        s = jnp.einsum("thd,shd->hts", qb, kr) / math.sqrt(d)
        seen = pb[:, None] >= pos[None, :]
        if window is not None:
            seen = seen & (pb[:, None] - pos[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", p, vv)               # (rows, n, 2d)

    o = jax.lax.map(block, (q.reshape(S // rows, rows, n, d),
                            pos.reshape(S // rows, rows))).reshape(S, n, 2 * d)
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + l0)
    o = o[:, : n // 2] - lam * o[:, n // 2:]
    o = (1.0 - l0) * rmsnorm(o, w["sub_norm/scale"], eps)
    return o.reshape(S, n * d)


def attention(h, w, cfg, mm, l0, window):
    """A layer with keys and values of its own, one sequence: (h, k, v)."""
    S = h.shape[0]
    n, m = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["hidden_size"]) // n
    x = layernorm(h, w, "input_norm", eps_of(cfg))
    q = (mm(x, w["q_proj/kernel"]) + w["q_proj/bias"]).reshape(S, n, d)
    k = (mm(x, w["k_proj/kernel"]) + w["k_proj/bias"]).reshape(S, m, d)
    v = (mm(x, w["v_proj/kernel"]) + w["v_proj/bias"]).reshape(S, m, d)
    o = differential(q, k, v, w, cfg, l0, window)
    return h + mm(o, w["o_proj/kernel"]) + w["o_proj/bias"], k, v


def cross_attention(h, w, cfg, mm, l0, k, v):
    S = h.shape[0]
    n = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"]) // n
    x = layernorm(h, w, "input_norm", eps_of(cfg))
    q = (mm(x, w["q_proj/kernel"]) + w["q_proj/bias"]).reshape(S, n, d)
    o = differential(q, k, v, w, cfg, l0, None)
    return h + mm(o, w["o_proj/kernel"]) + w["o_proj/bias"]


def gmu(h, w, cfg, mm, memory):
    x = layernorm(h, w, "input_norm", eps_of(cfg))
    return h + mm(jax.nn.silu(mm(x, w["in_proj/kernel"])) * memory,
                  w["o_proj/kernel"])


def mlp(h, w, cfg, mm):
    x = layernorm(h, w, "post_attn_norm", eps_of(cfg))
    return h + by_blocks(
        lambda xb: mm(jax.nn.silu(mm(xb, w["gate_proj/kernel"]))
                      * mm(xb, w["up_proj/kernel"]), w["down_proj/kernel"]), x)


def stacks(cfg) -> list:
    """[(stack name, None, number of entries)]: the subtrees whose leaves
    carry a layer axis."""
    kinds = layer_kinds(cfg)
    return [("layers", None, len(kinds))] + [
        (name, None, kinds.count(name)) for name in STACKS if name in kinds]


def hidden_states(cfg, ids, leaf, layer, control=None):
    """Hidden states (B, S, H) before the final norm. `leaf(path)` makes a
    leaf without a layer axis, as it is served; `layer(stack, l)` ->
    {leaf path: float32 array} one entry of a stack. Each kind of layer is
    one jitted program, a sequence at a time (`lax.map`), so only one
    layer's float32 weights are alive beside the memory and the shared keys
    and values."""
    L = int(cfg["num_hidden_layers"])
    window = int(cfg["sliding_window"])
    mm = matmul(control)

    def per_sequence(fn):
        """fn(h, w, *rest) of one sequence, over the batch: `w` and the
        layer's l0 shared, `h` and `rest` mapped."""
        return jax.jit(lambda h, w, l0, *rest: jax.lax.map(
            lambda xs: fn(xs[0], w, l0, *xs[1:]), (h, *rest)))

    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed/embedding").astype(F32), ids, axis=0)
        steps = {
            "mamba_layers": per_sequence(lambda h, w, l0: mamba(h, w, cfg, mm)),
            "gmu_layers": per_sequence(lambda h, w, l0, m: gmu(h, w, cfg, mm, m)),
            "window_attn_layers": per_sequence(
                lambda h, w, l0: attention(h, w, cfg, mm, l0, window)),
            "attn_layers": per_sequence(
                lambda h, w, l0: attention(h, w, cfg, mm, l0, None)),
            "cross_attn_layers": per_sequence(
                lambda h, w, l0, k, v: cross_attention(h, w, cfg, mm, l0, k, v)),
            "mlp": per_sequence(lambda h, w, l0: mlp(h, w, cfg, mm)),
        }
        seen = dict.fromkeys(STACKS, 0)
        memory = kv = None
        for i, kind in enumerate(layer_kinds(cfg)):
            w = dict(layer("layers", i))
            w.update(layer(kind, seen[kind]))
            seen[kind] += 1
            l0 = jnp.float32(lambda_init(i))
            if kind == "mamba_layers":
                h, y = steps[kind](h, w, l0)
                if i == L // 2:
                    memory = y
                del y
            elif kind == "gmu_layers":
                h = steps[kind](h, w, l0, memory)
            elif kind == "cross_attn_layers":
                h = steps[kind](h, w, l0, *kv)
            else:
                h, k, v = steps[kind](h, w, l0)
                if kind == "attn_layers":
                    kv = (k, v)
                del k, v
            h = steps["mlp"](h, w, l0)
        return h


def logits_at(cfg, h_rows, leaf, control=None):
    """Float32 logits (N, V) of the chosen rows (N, H) of the hidden states."""
    with jax.default_matmul_precision("highest"):
        w = {"final_norm/scale": leaf("final_norm/scale").astype(F32),
             "final_norm/bias": leaf("final_norm/bias").astype(F32)}
        x = layernorm(h_rows, w, "final_norm", eps_of(cfg))
        if cfg.get("tie_word_embeddings"):
            head = leaf("embed/embedding").astype(F32).T
        else:
            head = leaf("lm_head/kernel").astype(F32)
        return jax.jit(matmul(control))(x, head)
