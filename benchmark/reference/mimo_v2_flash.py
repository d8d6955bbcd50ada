"""Plain reference of the MiMoV2FlashForCausalLM decoder (MiMo-V2-Flash), for
the whole model or for ONE CHIP'S SHARE of its experts.

Straightforward `jax.numpy` in float32 with matrix products at "highest"
precision: no kernels, no cache, no pages, no ring, no batching tricks. It
imports nothing of the program under test. The layer equations (the config's
own keys; what the config does not carry is listed below):

    h = embed[ids]
    per layer i, of kind "window" where hybrid_layer_pattern[i] == 1, else "full":
      x = rmsnorm(h; input_norm)                         eps layernorm_epsilon
      q = x Wq   n heads of head_dim (192)               n, m, dv: the kind's own
      k = x Wk   m heads of head_dim                     (swa_* for a window layer)
      v = x Wv   m heads of v_head_dim (128), times attention_value_scale
      q, k = rope(first r dims of each head; the kind's theta), r =
             partial_rotary_factor x head_dim (64), the rest passes through
      s_j = q.k_j / sqrt(head_dim)  over the keys j a row t sees: j <= t and,
             in a window layer, t - j < sliding_window
      o = sum_j e^{s_j} v_j / (e^{sink_head} + sum_j e^{s_j})   (a window
             layer with add_swa_attention_sink_bias; a full one with
             add_full_attention_sink_bias; otherwise no e^{sink} term)
      h += o Wo
      x = rmsnorm(h; post_attn_norm)
      moe_layer_freq[i] == 0:  h += Wdown(silu(x Wgate) * x Wup)
      moe_layer_freq[i] == 1:  s = sigmoid(x Wr), float32, Wr as wide as the
                                   ROUTER is published
          T = top-k of (s + selection bias); w_e = scaling * s_e / sum_T s
          (scaling = routed_scaling_factor, null: 1)
          h += [shared(x), where n_shared_experts] + sum_{e in T and e HELD}
               w_e expert_e(x)
    logits = rmsnorm(h; final_norm) Whead

**The share.** The tree holds `n_routed_experts` experts, the first held one
`first_held_expert` (0). The router's width is the gate weight's own (the
configuration's `router_num_experts`); `w_e` is normalised over ALL the
chosen experts, held or not, and what the absent ones would add is left out:
that partial result goes on to the next layer, as it does on one chip of an
expert-parallel deployment before the exchange that this reference, like the
program, does not stand in for. With every expert held it is the whole model.

**Not carried by the config** (the configuration file lists them under
`assumed`): how the sink enters the softmax (the denominator alone, as
transformers' gpt-oss attention adds a per-head sink); where the value scale
acts (anywhere between Wv and Wo gives the same result; here right behind
Wv, in both kinds of layer, since the key has no `swa_` prefix); which
dimensions rotate (the first r of each head, half-split: the first r/2
against the next r/2); `attention_chunk_size` (128) read as the published
code's block for the window, not as a second mask. The multi-token-
prediction layers are not written. Every held expert is computed for every
token and the unselected weighted 0 (plain, and exact). Attention runs a
block of query rows at a time (`lax.map` over sequences and blocks: 64 heads
x 8,192^2 float32 scores do not fit otherwise), which changes no number.

The reference asks for its own leaves, as the others do: `leaf(path)` for
one without a layer axis, `layer(stack, l)` for one layer's. A layer's
norms and MLP lie in `dense_layers` / `moe_layers`, its attention in its
kind's stack (`attn_layers` for the full layers, `window_attn_layers` for
the window layers), entry j the j-th layer of that kind. The control
(`control="int8"` or `"fp8"`) rounds both operands of every weight matrix
product (projections, experts, head; not the router) to the lower precision
first. It exists to show that `correct` fails for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows of one block of attention scores, tokens of one block of an MLP
ROWS = 256

#: how the benchmark draws the leaves only this architecture has: a sink
#: logit a head near ln 128, so that the sink weighs about as much as a
#: whole window of 128 keys at scores near 0 (N(0, 1) would weigh one key in
#: 128, and a step that dropped the sink would pass unseen)
LEAF_RULES = {"sinks": (4.85, 1.0)}


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


def rope(x, positions, theta, r):
    """x (S, heads, d): rotate the first r dims of each head, the first r/2
    against the next r/2; the rest pass through."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=F32) / r)
    ang = positions[:, None].astype(F32) * inv_freq          # (S, r/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def blocks_of(n: int) -> int:
    """Rows of a block: `ROWS`, or all `n` where `ROWS` does not divide it."""
    return ROWS if n % ROWS == 0 else n


def geometry(cfg, window: bool) -> dict:
    """A kind's heads, key/value heads, key and value widths, rope theta and
    whether it has sinks."""
    d = int(cfg["head_dim"])
    n, m = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dv = int(cfg.get("v_head_dim") or d)
    if not window:
        return dict(n=n, m=m, d=d, dv=dv, theta=float(cfg["rope_theta"]),
                    sinks=bool(cfg.get("add_full_attention_sink_bias")))
    assert int(cfg.get("swa_head_dim") or d) == d
    return dict(n=int(cfg.get("swa_num_attention_heads") or n),
                m=int(cfg.get("swa_num_key_value_heads") or m), d=d,
                dv=int(cfg.get("swa_v_head_dim") or dv),
                theta=float(cfg.get("swa_rope_theta", cfg["rope_theta"])),
                sinks=bool(cfg.get("add_swa_attention_sink_bias")))


def attention(h, w, cfg, mm, window):
    """One sequence, h (S, H). `window` None: a full layer."""
    S = h.shape[0]
    g = geometry(cfg, window is not None)
    n, m, d, dv = g["n"], g["m"], g["d"], g["dv"]
    r = int(d * float(cfg.get("partial_rotary_factor", 1.0)))
    r -= r % 2
    pos = jnp.arange(S)
    x = rmsnorm(h, w["input_norm/scale"], float(cfg["layernorm_epsilon"]))
    q = rope(mm(x, w["q_proj/kernel"]).reshape(S, n, d), pos, g["theta"], r)
    k = rope(mm(x, w["k_proj/kernel"]).reshape(S, m, d), pos, g["theta"], r)
    v = mm(x, w["v_proj/kernel"]).reshape(S, m, dv) * float(
        cfg.get("attention_value_scale") or 1.0)
    sink = w["sinks"].reshape(m, n // m, 1, 1) if g["sinks"] else None
    rows = blocks_of(S)

    def block(args):
        qb, pb = args                                    # (rows, n, d), (rows,)
        qg = qb.reshape(rows, m, n // m, d)
        s = jnp.einsum("tkgd,skd->kgts", qg, k) * d ** -0.5
        seen = pb[:, None] >= pos[None, :]
        if window is not None:
            seen = seen & (pb[:, None] - pos[None, :] < window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        if sink is not None:
            # the sink joins the denominator and is dropped from the output
            s = jnp.concatenate([s, jnp.broadcast_to(sink, s.shape[:3] + (1,))], -1)
        p = jax.nn.softmax(s, -1)[..., :S]
        return jnp.einsum("kgts,skd->tkgd", p, v).reshape(rows, n * dv)

    out = jax.lax.map(
        block, (q.reshape(S // rows, rows, n, d), pos.reshape(S // rows, rows)))
    return h + mm(out.reshape(S, n * dv), w["o_proj/kernel"])


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
    assert int(cfg.get("n_group", 1)) == 1 and int(cfg.get("topk_group", 1)) == 1
    assert cfg["scoring_func"] == "sigmoid"
    scores = jax.nn.sigmoid(x @ w["moe/gate/weight"])
    select = scores + w.get("moe/gate/e_score_bias", 0.0)
    _, top = jax.lax.top_k(select, K)
    chosen = jnp.take_along_axis(scores, top, -1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    chosen = chosen * float(cfg.get("routed_scaling_factor") or 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(scores.shape, F32).at[rows, top].set(chosen)


def expert_mlp(x, w, cfg, mm):
    """The HELD experts' part of the routed sum (and the shared experts',
    where the configuration has them), x (T, H)."""
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
    if not cfg.get("n_shared_experts"):
        return routed
    return routed + gated_mlp(x, w["moe/shared/gate_proj/kernel"],
                              w["moe/shared/up_proj/kernel"],
                              w["moe/shared/down_proj/kernel"], mm)


def decoder_layer(h, w, cfg, mm, window, sparse):
    """One sequence through one layer, h (S, H)."""
    h = attention(h, w, cfg, mm, window)
    x = rmsnorm(h, w["post_attn_norm/scale"], float(cfg["layernorm_epsilon"]))
    if sparse:
        return h + by_blocks(lambda xb: expert_mlp(xb, w, cfg, mm), x)
    return h + by_blocks(
        lambda xb: gated_mlp(xb, w["gate_proj/kernel"], w["up_proj/kernel"],
                             w["down_proj/kernel"], mm), x)


def num_dense(cfg) -> int:
    freq = list(cfg["moe_layer_freq"])
    k = freq.index(1) if 1 in freq else len(freq)
    assert freq == [0] * k + [1] * (len(freq) - k), freq
    return k


def is_window(cfg, i: int) -> bool:
    return cfg["hybrid_layer_pattern"][i] == 1


def stacks(cfg) -> list:
    """[(stack name, layer function, number of layers)]: the layers' norms
    and MLPs in order, then each kind's attention."""
    L = int(cfg["num_hidden_layers"])
    k = num_dense(cfg)
    n_window = sum(is_window(cfg, i) for i in range(L))
    out = [("dense_layers", decoder_layer, k)] if k else []
    out.append(("moe_layers", decoder_layer, L - k))
    if L - n_window:
        out.append(("attn_layers", None, L - n_window))
    if n_window:
        out.append(("window_attn_layers", None, n_window))
    return out


def hidden_states(cfg, ids, leaf, layer, control=None):
    """Final hidden states (B, S, H), before the last norm. `leaf(path)` makes
    a leaf that has no layer axis, by its path in the program's tree, as it is
    served; `layer(stack, l)` -> {leaf path: float32 array} makes one layer's.
    Each kind of layer is one jitted program, a sequence at a time, so only
    one layer's float32 weights and one sequence's activations are alive."""
    L = int(cfg["num_hidden_layers"])
    assert len(cfg["hybrid_layer_pattern"]) == L
    k = num_dense(cfg)
    window = int(cfg["sliding_window"])
    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed/embedding").astype(F32), ids, axis=0)
        steps: dict = {}
        seen = {"attn_layers": 0, "window_attn_layers": 0}
        for i in range(L):
            op = "window_attn_layers" if is_window(cfg, i) else "attn_layers"
            w = dict(layer(*(("dense_layers", i) if i < k
                             else ("moe_layers", i - k))))
            w.update(layer(op, seen[op]))
            seen[op] += 1
            kind = (window if is_window(cfg, i) else None, i >= k)
            if kind not in steps:
                steps[kind] = jax.jit(lambda h, w, kind=kind: jax.lax.map(
                    lambda seq: decoder_layer(seq, w, cfg, matmul(control),
                                              *kind), h))
            h = steps[kind](h, w)
        return h


def logits_at(cfg, h_rows, leaf, control=None):
    """Float32 logits (N, V) of the chosen rows (N, H) of the hidden states."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(h_rows, leaf("final_norm/scale").astype(F32),
                    float(cfg["layernorm_epsilon"]))
        return jax.jit(matmul(control))(x, leaf("lm_head/kernel").astype(F32))
