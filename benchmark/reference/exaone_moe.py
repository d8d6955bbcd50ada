"""Plain reference of the ExaoneMoeForCausalLM decoder (K-EXAONE-236B-A23B),
for the whole model or for ONE CHIP'S SHARE of its experts.

Straightforward `jax.numpy` in float32 with matrix products at "highest"
precision: no kernels, no cache, no pages, no ring, no batching tricks. It
imports nothing of the program under test. The layer equations (the config's
own keys; what the config does not carry is listed below):

    h = embed[ids]
    per layer i:
      x = rmsnorm(h; input_norm)
      q = rmsnorm_head(x Wq), k = rmsnorm_head(x Wk), v = x Wv
                               64 / 8 / 8 heads of 128; one scale vector of
                               128 for q and one for k
      layer_types[i] == "sliding_attention":
          q, k = rope(q, k; theta); row t attends s with 0 <= t - s < window
      layer_types[i] == "full_attention":
          NO rotary embedding;     row t attends every s <= t
      h += Wo softmax(q k^T / sqrt(head_dim)) v      grouped, no bias, no sinks
      x = rmsnorm(h; post_attn_norm)
      mlp_layer_types[i] == "dense":   h += Wdown(silu(x Wgate) * x Wup)
      mlp_layer_types[i] == "sparse":  s = sigmoid(x Wr), float32, Wr as wide
                                           as the ROUTER is published
          T = top-k of (s + selection bias); w_e = scaling * s_e / sum_T s
          h += shared(x) + sum_{e in T and e HELD} w_e expert_e(x)
    logits = rmsnorm(h; final_norm) Whead

**The share.** The tree holds `num_experts` experts, the first held one
`first_held_expert` (0). The router's width is the gate weight's own (the
configuration's `router_num_experts`); `w_e` is normalised over ALL the
chosen experts, held or not, and what the absent ones would add is left out:
that partial result goes on to the next layer, as it does on one chip of an
expert-parallel deployment before the exchange that this reference, like the
program, does not stand in for. With every expert held it is the whole model.

**From the family's modelling code, not from the config** (the configuration
file lists them under `assumed`): the per-head RMSNorm on q and k and the
rotary embedding on window layers only (transformers `modeling_exaone4.py`
`Exaone4Attention`, which the family keeps); the two layer norms BEFORE each
sublayer. The multi-token-prediction module is not part of the 48 layers and
is not written. Rope rotates the two halves of a head against each other
("half-split", the layout the program's checkpoint adapter converts to).
Every held expert is computed for every token and the unselected weighted 0
(plain, and exact). Attention runs a block of query rows at a time (`lax.map`
over sequences and blocks: 64 heads x 8,192^2 float32 scores do not fit
otherwise), which changes no number.

The reference asks for its own leaves, as the others do: `leaf(path)` for
one without a layer axis, `layer(stack, l)` for one layer's. The control
(`control="int8"` or `"fp8"`) rounds both operands of every weight matrix
product (projections, experts, shared expert, head; not the router) to the
lower precision first. It exists to show that `correct` fails for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows of one block of attention scores, tokens of one block of an MLP
ROWS = 256


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
    """x (S, heads, d): rotate the first half of d against the second."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[:, None].astype(F32) * inv_freq          # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def blocks_of(n: int) -> int:
    """Rows of a block: `ROWS`, or all `n` where `ROWS` does not divide it."""
    return ROWS if n % ROWS == 0 else n


def attention(h, w, cfg, mm, window):
    """One sequence, h (S, H). `window` None: full attention, no rope."""
    S = h.shape[0]
    n, m, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(S)
    x = rmsnorm(h, w["input_norm/scale"], eps)
    q = rmsnorm(mm(x, w["q_proj/kernel"]).reshape(S, n, d), w["q_norm/scale"], eps)
    k = rmsnorm(mm(x, w["k_proj/kernel"]).reshape(S, m, d), w["k_norm/scale"], eps)
    v = mm(x, w["v_proj/kernel"]).reshape(S, m, d)
    if window is not None:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    rows = blocks_of(S)

    def block(args):
        qb, pb = args                                    # (rows, n, d), (rows,)
        qg = qb.reshape(rows, m, n // m, d)
        s = jnp.einsum("tkgd,skd->kgts", qg, k) * d ** -0.5
        seen = pb[:, None] >= pos[None, :]
        if window is not None:
            seen = seen & (pb[:, None] - pos[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgts,skd->tkgd", p, v).reshape(rows, n * d)

    out = jax.lax.map(
        block, (q.reshape(S // rows, rows, n, d), pos.reshape(S // rows, rows)))
    return h + mm(out.reshape(S, n * d), w["o_proj/kernel"])


def gated_mlp(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def by_blocks(fn, x):
    """`fn` over blocks of x's rows: an MLP's inner width times a whole
    sequence of float32 rows is gigabytes."""
    S, H = x.shape
    rows = blocks_of(S)
    return jax.lax.map(fn, x.reshape(S // rows, rows, H)).reshape(S, H)


def route(x, w, cfg):
    """(T, E routed) combine weights: 0 for the experts a token does not
    use. The router's width is the gate weight's own."""
    K = cfg["num_experts_per_tok"]
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
    return jnp.zeros(scores.shape, F32).at[rows, top].set(chosen)


def expert_mlp(x, w, cfg, mm):
    """shared(x) + the HELD experts' part of the routed sum, x (T, H)."""
    held = w["moe/experts/gate_proj/kernel"].shape[0]
    first = int(cfg.get("first_held_expert", 0))
    combine = route(x, w, cfg)[:, first:first + held]

    def one_expert(acc, e):
        gate, up, down, weight = e
        return acc + weight[:, None] * gated_mlp(x, gate, up, down, mm), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["moe/experts/gate_proj/kernel"], w["moe/experts/up_proj/kernel"],
         w["moe/experts/down_proj/kernel"], combine.T),
    )
    shared = gated_mlp(x, w["moe/shared/gate_proj/kernel"],
                       w["moe/shared/up_proj/kernel"],
                       w["moe/shared/down_proj/kernel"], mm)
    return routed + shared


def decoder_layer(h, w, cfg, mm, window, sparse):
    """One sequence through one layer, h (S, H)."""
    h = attention(h, w, cfg, mm, window)
    x = rmsnorm(h, w["post_attn_norm/scale"], cfg["rms_norm_eps"])
    if sparse:
        return h + by_blocks(lambda xb: expert_mlp(xb, w, cfg, mm), x)
    return h + by_blocks(
        lambda xb: gated_mlp(xb, w["gate_proj/kernel"], w["up_proj/kernel"],
                             w["down_proj/kernel"], mm), x)


def num_dense(cfg) -> int:
    types = cfg.get("mlp_layer_types")
    if types is None:
        return int(cfg.get("first_k_dense_replace", 0))
    k = sum(t == "dense" for t in types)
    assert list(types) == ["dense"] * k + ["sparse"] * (len(types) - k), types
    return k


def stacks(cfg) -> list:
    """[(stack name, layer function, number of layers)] in order."""
    k = num_dense(cfg)
    out = [("dense_layers", decoder_layer, k)] if k else []
    return out + [("moe_layers", decoder_layer, cfg["num_hidden_layers"] - k)]


def layer_window(cfg, i: int):
    """Layer i's window, None for a full-attention layer."""
    types = cfg.get("layer_types")
    if not cfg.get("sliding_window") or not types:
        return None
    return int(cfg["sliding_window"]) if types[i] == "sliding_attention" else None


def hidden_states(cfg, ids, leaf, layer, control=None):
    """Final hidden states (B, S, H), before the last norm. `leaf(path)` makes
    a leaf that has no layer axis, by its path in the program's tree, as it is
    served; `layer(stack, l)` -> {leaf path: float32 array} makes one layer's.
    Each kind of layer is one jitted program, a sequence at a time, so only
    one layer's float32 weights and one sequence's activations are alive."""
    assert len(cfg.get("layer_types") or ()) in (0, cfg["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed/embedding").astype(F32), ids, axis=0)
        steps: dict = {}
        i = 0
        for stack, fn, n in stacks(cfg):
            for l in range(n):
                kind = (layer_window(cfg, i), stack == "moe_layers")
                if kind not in steps:
                    steps[kind] = jax.jit(lambda h, w, kind=kind: jax.lax.map(
                        lambda seq: fn(seq, w, cfg, matmul(control), *kind), h))
                h = steps[kind](h, layer(stack, l))
                i += 1
        return h


def logits_at(cfg, h_rows, leaf, control=None):
    """Float32 logits (N, V) of the chosen rows (N, H) of the hidden states."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(h_rows, leaf("final_norm/scale").astype(F32),
                    cfg["rms_norm_eps"])
        return jax.jit(matmul(control))(x, leaf("lm_head/kernel").astype(F32))
