"""Seeded weights, made on the device by the benchmark and by nothing else.

The program under test and the plain reference are both handed weights from
here; neither makes its own and neither sees the other's. A leaf is a pure
function of (seed, its path in the parameter tree, its layer), so the
reference can make one layer's leaves at a time after the program's copy has
been freed, and gets the same numbers.

Distribution (the benchmark's choice, stated in PERF.md): kernels are normal
with std fan_in**-0.5; the projections that write into the residual stream
(`o_proj`, `down_proj`) are scaled down by (2 * published depth)**-0.5, the
GPT-2 / Megatron "scaled init", so that the stream does not grow with depth;
the embedding has std 1; norm scales are 1 + 0.1 N; a `bias` and the router's
selection bias are 0.02 N. A leaf that only one architecture has is drawn by
that architecture's reference module: its optional `LEAF_RULES`, {last path
component: (mean, std)}. Which subtrees carry a leading layer axis is the
reference's to say too (`stacks(cfg)`). Every value is rounded to the dtype
it is served in.
"""

from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Draw:
    """How one configuration's tree is drawn."""
    depth: int      # the PUBLISHED depth: the scaled init does not follow a cut
    stacks: tuple   # subtrees whose leaves have a leading layer axis
    rules: dict     # the architecture's own leaves: {last component: (mean, std)}


def draw_for(ref, config: dict) -> Draw:
    """`ref`: the configuration's reference module."""
    return Draw(int(config["published"]["num_hidden_layers"]),
                tuple(name for name, *_ in ref.stacks(config)),
                dict(getattr(ref, "LEAF_RULES", {})))


def root_key(seed: int) -> jax.Array:
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _path_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def leaf_rule(path: str, shape: tuple, draw: Draw) -> tuple[float, float]:
    """(mean, std) of the leaf at `path`; `shape` is one layer's shape."""
    name = path.split("/")
    if name[-1] == "scale":
        return 1.0, 0.1
    if name[-1] in ("e_score_bias", "bias"):
        return 0.0, 0.02
    if name[-1] == "embedding":
        return 0.0, 1.0
    if name[-1] in ("kernel", "weight"):
        std = shape[-2] ** -0.5
        if name[-2] in ("o_proj", "down_proj"):
            std *= (2.0 * draw.depth) ** -0.5
        return 0.0, std
    if name[-1] in draw.rules:
        mean, std = draw.rules[name[-1]]
        return float(mean), float(std)
    raise KeyError(f"no rule for the weight leaf {path!r}: give its "
                   "reference module a LEAF_RULES entry")


def make_leaf(key: jax.Array, path: str, shape: tuple, dtype, draw: Draw,
              layer: int | jax.Array | None = None) -> jax.Array:
    """One leaf (one layer of it where `layer` is given), traceable."""
    k = _path_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    mean, std = leaf_rule(path, shape, draw)
    x = mean + std * jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


def tree_paths(shapes: dict, prefix: str = "") -> dict:
    """{"a/b/c": ShapeDtypeStruct} of a nested dict of shapes."""
    out = {}
    for name, sub in shapes.items():
        path = f"{prefix}/{name}" if prefix else name
        if isinstance(sub, dict):
            out.update(tree_paths(sub, path))
        else:
            out[path] = sub
    return out


def make_params(seed: int, shapes: dict, dtype, draw: Draw) -> dict:
    """The whole tree the program asks for (`shapes`: its own
    `jax.eval_shape(init)`), in `dtype`, in ONE jitted call. A stacked leaf
    is filled layer by layer (`lax.map`), so layer l of it is what
    `make_leaf(..., layer=l)` gives the reference (to one unit in the last
    place of float32, before the rounding to `dtype`: see the test)."""
    flat = tree_paths(shapes)

    def build(key):
        out = {}
        for path, s in flat.items():
            if path.split("/")[0] in draw.stacks:
                one = tuple(s.shape[1:])
                out[path] = jax.lax.map(
                    lambda l, path=path, one=one: make_leaf(
                        key, path, one, dtype, draw, l),
                    jnp.arange(s.shape[0]),
                )
            else:
                out[path] = make_leaf(key, path, tuple(s.shape), dtype, draw)
        return out

    made = jax.jit(build)(root_key(seed))
    tree: dict = {}
    for path, leaf in made.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make_layer(seed_key: jax.Array, shapes_flat: dict, stack: str, layer: int,
               draw: Draw, dtype) -> dict:
    """The leaves of one layer of `stack`, rounded to `dtype` as served and
    widened to float32 for the reference: {"moe/gate/weight": array}."""
    out = {}
    for path, s in shapes_flat.items():
        if path.split("/")[0] != stack:
            continue
        leaf = make_leaf(seed_key, path, tuple(s.shape[1:]), dtype, draw, layer)
        out[path[len(stack) + 1:]] = leaf.astype(jnp.float32)
    return out
