"""What decides `correct` for a served model: once the window has closed and
the program's state is freed, a sample of the requests it finished (drawn from
the seed, the longest among them) is followed by the plain reference, which
runs once over each prompt with its served tokens. Numbers read per served
token, all in the reference's float32 logits:

  gap          reference's best logit minus its logit of the served token
  logprob_err  |log-probability the serve step itself reported for the token
               it chose  -  the reference's log-probability of that token|

and their order statistics over the sample. Which of them are compared, and
each one's limit, is data: `limits/<workload>.json`.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmark import find_data, load_module, weights


def load_limits(workload: str, root: str, paths: list) -> dict:
    path = find_data(root, paths, "limits", f"{workload}.json")
    if path is None:
        return {}
    with open(path) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def pick_sample(finished: list, n: int, seed: int) -> list:
    """The longest finished request and n - 1 others drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i].prompt) + len(finished[i].tokens)))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0xC0DE])
    extra = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [finished[order[0]]] + [finished[rest[i]] for i in sorted(extra)]


def load_reference(config: dict, root: str, paths: list):
    """The configuration's plain reference: `reference/<name>.py` under the
    manifest's `paths`, by the name in the configuration's file."""
    return load_module(root, paths, "reference", config["reference"])


def reference_logits(ref, config: dict, shapes: dict, seed: int, sample: list,
                     pad_to: int, control: str | None = None):
    """Float32 logits (N, V) at every position that produced a served token
    of the sample, in order, from the plain reference `ref` alone (or from
    the control: the reference in the lower precision `control`). The
    reference asks for each leaf by its path in the program's tree; what
    it gets is made here from the seed, never taken from the program."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["serve_dtype"])
    draw = weights.draw_for(ref, config)
    flat = weights.tree_paths(shapes)
    key = weights.root_key(seed)
    longest = max(len(r.prompt) + len(r.tokens) for r in sample)
    width = max(int(pad_to), -(-longest // 128) * 128)
    ids = np.zeros((len(sample), width), np.int32)
    rows, cols = [], []
    for i, r in enumerate(sample):
        seq = list(r.prompt) + list(r.tokens)
        ids[i, : len(seq)] = seq
        # position p predicts token p + 1
        cols += list(range(len(r.prompt) - 1, len(seq) - 1))
        rows += [i] * len(r.tokens)

    # the key goes in as an argument: a constant would make every seed a new
    # program, and no run would find the reference's programs in the cache
    def leaf(path):
        return jax.jit(lambda k: weights.make_leaf(
            k, path, tuple(flat[path].shape), dtype, draw))(key)

    make = jax.jit(
        lambda k, stack, l: weights.make_layer(k, flat, stack, l, draw, dtype),
        static_argnums=1)
    h = ref.hidden_states(config, jnp.asarray(ids), leaf,
                          lambda stack, l: make(key, stack, l), control)
    h_rows = h[np.asarray(rows), np.asarray(cols)]
    del h
    return ref.logits_at(config, h_rows, leaf, control)


def logsumexp(logits: np.ndarray) -> np.ndarray:
    best = logits.max(-1)
    return best + np.log(np.exp(logits - best[:, None]).sum(-1))


def check(ref, config: dict, mix: dict, seed: int, recs: list, logprobs: dict,
          window: dict, shapes: dict, limits: dict,
          control: str | None = None) -> dict:
    """{"correct": bool, "numbers": {name: {"value", "limit"}}, "observed":
    {...every statistic read...}}. With `control` the reference in that lower
    precision is put in the program's place: at each position of the same
    prompts and served tokens, the token it puts first and the log-probability
    it gives that token are read instead of the program's."""
    import jax

    t0, t1 = window["t0"], window["t1"]
    finished = [r for r in recs
                if r.reason == "length" and t0 <= r.times[-1] < t1]
    sample = pick_sample(finished, int(mix["check"]["requests"]), seed)
    observed: dict = {"finished_in_window": len(finished),
                      "requests_followed": len(sample)}
    if sample:
        logits = reference_logits(ref, config, shapes, seed, sample,
                                  mix["check"]["pad_to"])
        served = np.concatenate([np.asarray(r.tokens, np.int64) for r in sample])
        logits = jax.device_get(logits).astype(np.float64)
        stepped = [p for r in sample for p in logprobs.get(r.rid, [])]
        if control:
            low = jax.device_get(reference_logits(
                ref, config, shapes, seed, sample, mix["check"]["pad_to"],
                control)
            ).astype(np.float64)
            served = low.argmax(-1)
            low_lp = low[np.arange(len(served)), served] - logsumexp(low)
            stepped = list(zip(served.tolist(), low_lp.tolist()))
            del low
        best = logits.max(-1)
        at = logits[np.arange(len(served)), served]
        lse = logsumexp(logits)
        gap = best - at
        # the tokens the clients received are the tokens the steps sampled
        mismatch = (len(stepped) != len(served)) + sum(
            int(a != b) for (a, _), b in zip(stepped, served))
        observed["delivery_mismatch"] = float(mismatch)
        observed["tokens_followed"] = float(len(served))
        observed["longest_followed"] = float(max(
            len(r.prompt) + len(r.tokens) for r in sample))
        observed["gap_max"] = float(gap.max())
        observed["gap_mean"] = float(gap.mean())
        observed["gap_p90"] = float(np.percentile(gap, 90))
        observed["share_not_best"] = float((gap > 0).mean())
        observed["ref_logit_std"] = float(logits.std())
        if len(stepped) == len(served):
            err = np.abs(np.array([lp for _, lp in stepped]) - (at - lse))
            for q in (25, 50, 75, 90):
                observed[f"logprob_err_p{q}"] = float(np.percentile(err, q))
            observed["logprob_err_mean"] = float(err.mean())
            observed["logprob_err_max"] = float(err.max())
    numbers, correct = {}, bool(sample) and bool(limits)
    for name, entry in limits.items():
        value = observed.get(name)
        ok = value is not None and np.isfinite(value) and value <= entry["limit"]
        numbers[name] = {"value": value, "limit": entry["limit"]}
        correct = correct and ok
    return {"correct": correct, "numbers": numbers, "observed": observed}
