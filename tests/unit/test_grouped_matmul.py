"""The grouped matmul of the routed experts: the Pallas kernel (interpret
mode on the CPU) against `lax.ragged_dot`, the dispatcher's rule and its
counter, and the MoE paths with the dispatcher forced to either side.

Interpret mode fills an output buffer and a ragged block's padding with NaN
(`pallas.primitives.uninitialized_value`), so a row the kernel leaves
unwritten reads back as NaN here: "the rows past the last group are exact
zeros" is tested against a NaN buffer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import automodel_tpu.ops.grouped_matmul as gmm
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.observability.metrics import default_registry
from automodel_tpu.ops.pallas.grouped_matmul import (
    group_visits,
    grouped_matmul_kernel,
)

#: group-size plans over 4 groups and 80 rows, walked with a 32-row tile (80
#: is not a multiple of it: the last tile is ragged)
PLANS = {
    "even, 20 a group": [20, 20, 20, 20],
    "empty groups": [0, 50, 0, 30],
    "every row on one expert": [0, 0, 80, 0],
    "straddling tiles": [31, 2, 33, 14],
    "rows past the last group": [5, 0, 37, 9],
    "no group at all": [0, 0, 0, 0],
}
#: the cell's two operand orientations (gate / up, and down)
ORIENTATIONS = {"k2048_n1408": (2048, 1408), "k1408_n2048": (1408, 2048)}


def _operands(m, k, n, E, dtype, seed=0):
    ka, kb = jax.random.split(jax.random.key(seed))
    lhs = jax.random.normal(ka, (m, k), dtype)
    rhs = jax.random.normal(kb, (E, k, n), dtype) * k ** -0.5
    return lhs, rhs


@pytest.mark.parametrize("orientation", ORIENTATIONS.values(), ids=ORIENTATIONS.keys())
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("sizes", PLANS.values(), ids=PLANS.keys())
def test_kernel_matches_ragged_dot(sizes, dtype, orientation):
    k, n = orientation
    m, total = 80, sum(sizes)
    lhs, rhs = _operands(m, k, n, len(sizes), dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    out = grouped_matmul_kernel(lhs, rhs, group_sizes, tm=32, tn=n)
    assert out.shape == (m, n) and out.dtype == dtype
    out = np.asarray(out, np.float32)
    ref = np.asarray(jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(out[:total], ref[:total], rtol=tol, atol=tol)
    # exact zeros where the buffer held NaN
    assert (out[total:] == 0).all()


def test_kernel_walks_slabs_narrower_than_n_and_the_dispatchers_tiles():
    """`tn` below n (a ragged last slab: 384 = 256 + 128), and the tiles the
    dispatcher gives the same call."""
    lhs, rhs = _operands(80, 256, 384, 4, jnp.float32)
    group_sizes = jnp.asarray([31, 2, 33, 9], jnp.int32)
    ref = np.asarray(jax.lax.ragged_dot(lhs, rhs, group_sizes))
    for tm, tn in [(16, 256), gmm.tiles(80, 256, 384, jnp.float32)]:
        out = np.asarray(grouped_matmul_kernel(lhs, rhs, group_sizes, tm=tm, tn=tn))
        np.testing.assert_allclose(out[:75], ref[:75], rtol=1e-4, atol=1e-4)
        assert (out[75:] == 0).all()


def test_group_visits_fetch_each_live_group_once_and_skip_empty_ones():
    offsets, visits, count = group_visits(
        jnp.asarray([31, 0, 35, 0, 6], jnp.int32), 80, 32)
    group, tile, slab = np.asarray(visits[:, :int(count)])
    # group 0 in tile 0; group 2 over tiles 0-2; group 4 in tile 2; the tail
    # (group 5: rows 72-79) in tile 2, on the last live group's slab
    assert group.tolist() == [0, 2, 2, 2, 4, 5]
    assert tile.tolist() == [0, 0, 1, 2, 2, 2]
    assert slab.tolist() == [0, 2, 2, 2, 4, 4]
    assert np.asarray(offsets).tolist() == [0, 31, 31, 66, 66, 72, 80]
    # a slab index changes only where the group does: one fetch a live group
    assert (np.diff(slab) != 0).sum() + 1 == 3


def test_gradients_are_ragged_dots_own():
    lhs, rhs = _operands(48, 128, 256, 4, jnp.bfloat16, seed=3)
    group_sizes = jnp.asarray([10, 0, 25, 9], jnp.int32)

    def loss(fn, a, b):
        return jnp.sum(jnp.sin(fn(a, b, group_sizes).astype(jnp.float32)))

    kernel = lambda a, b, g: gmm.grouped_matmul(a, b, g, impl="pallas")  # noqa: E731
    got = jax.grad(loss, argnums=(1, 2))(kernel, lhs, rhs)
    want = jax.grad(loss, argnums=(1, 2))(jax.lax.ragged_dot, lhs, rhs)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        _assert_close(a, b)


def _assert_close(got, want, tol=2.0 ** -6):
    """bf16 results a few roundoffs of the largest magnitude apart."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def _calls(**labels):
    return default_registry().counter("grouped_matmul_calls_total", **labels).value


def test_dispatch_rule_reads_shapes_alone_and_every_call_site_is_counted(monkeypatch, caplog):
    bf16 = jnp.bfloat16
    s = jax.ShapeDtypeStruct
    def reason(lhs, rhs, sharded=False):
        return gmm._unsupported_reason(lhs, rhs, sharded)

    serve = s((1536, 2048), bf16), s((64, 2048, 1408), bf16)
    assert reason(*serve) is None
    assert reason(s((1536, 1408), bf16), s((64, 1408, 2048), bf16)) is None
    # a training batch's 384 rows a group: no clause on the rows
    assert reason(s((24576, 2048), bf16), serve[1]) is None
    assert "GSPMD" in reason(*serve, sharded=True)
    assert "not bfloat16" in reason(s((1536, 2048), jnp.float32), serve[1])
    assert "not bfloat16" in reason(
        s((1536, 2048), jnp.float32), s((64, 2048, 1408), jnp.float32))
    assert "lanes" in reason(s((1536, 2000), bf16), s((64, 2000, 1408), bf16))
    assert "slab" in reason(s((64, 2**17), bf16), s((64, 2**17, 128), bf16))
    # tiles: the whole (k, n) where 16 MiB takes it, else the lanes that fit
    assert gmm.tiles(1536, 2048, 1408, bf16) == (128, 1408)
    assert gmm.tiles(1536, 1408, 2048, bf16) == (128, 2048)
    assert gmm.tiles(40, 2048, 1408, bf16) == (48, 1408)
    assert gmm.tiles(40, 2048, 1408, jnp.float32) == (40, 1408)
    assert gmm.tiles(8192, 7168, 2048, bf16) == (128, 1152)

    lhs, rhs = _operands(32, 128, 128, 4, bf16)
    group_sizes = jnp.asarray([8, 8, 8, 8], jnp.int32)
    ref = jax.lax.ragged_dot(lhs, rhs, group_sizes)

    def ticks(fn, **labels):
        before = _calls(**labels)
        _assert_close(fn(), ref)
        return _calls(**labels) - before

    # off the TPU "auto" is the reference; one tick a traced call site
    assert ticks(lambda: gmm.grouped_matmul(lhs, rhs, group_sizes),
                 impl="xla", reason="no TPU") == 1
    jitted = jax.jit(gmm.grouped_matmul)
    assert ticks(lambda: (jitted(lhs, rhs, group_sizes), jitted(lhs, rhs, group_sizes))[1],
                 impl="xla", reason="no TPU") == 1
    # on a TPU a qualifying call is the kernel's, any other the reference's,
    # logged the first time
    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    assert ticks(lambda: gmm.grouped_matmul(lhs, rhs, group_sizes),
                 impl="pallas", reason="bf16 call on a TPU") == 1
    with caplog.at_level("WARNING", logger=gmm.logger.name):
        assert ticks(
            lambda: gmm.grouped_matmul(lhs, rhs, group_sizes, mesh_ctx=_Mesh(4)),
            impl="xla", reason="operands sharded under GSPMD") == 1
    assert any("lax.ragged_dot on this TPU" in r.message for r in caplog.records)
    with pytest.raises(NotImplementedError, match="GSPMD"):
        gmm.grouped_matmul(lhs, rhs, group_sizes, impl="pallas", mesh_ctx=_Mesh(4))
    with pytest.raises(ValueError, match="Unknown"):
        gmm.grouped_matmul(lhs, rhs, group_sizes, impl="flash")


@dataclasses.dataclass
class _Mesh:
    num_devices: int


MOE = MoEConfig(
    n_routed_experts=8, experts_per_token=2, moe_intermediate_size=128,
    dispatcher="dropless",
)


def _routed(T=64, H=128, seed=0):
    """Expert weights and a routing with masked (sentinel) tokens and half
    the rows forced onto one expert."""
    from automodel_tpu.moe.experts import init_experts
    from automodel_tpu.moe.gate import gate_forward, init_gate

    params = init_experts(MOE, H, jax.random.key(seed))
    gate = init_gate(MOE, H, jax.random.key(seed + 1))
    x = jax.random.normal(jax.random.key(seed + 2), (T, H), jnp.float32)
    mask = jnp.ones((T,), bool).at[-3:].set(False)
    w, idx, _, _ = gate_forward(gate, MOE, x, mask)
    # bf16 rows: what the rule hands the kernel on a TPU
    return params, x.astype(jnp.bfloat16), w, idx.at[: T // 2, 0].set(3)


def test_dropless_experts_give_the_same_output_on_either_side(monkeypatch):
    from automodel_tpu.moe.experts import experts_forward_dropless

    params, x, w, idx = _routed()

    def run():
        fwd = lambda p: experts_forward_dropless(p, MOE, x, w, idx)  # noqa: E731
        return fwd(params), jax.grad(lambda p: jnp.sum(fwd(p) ** 2))(params)

    ref, ref_grads = run()
    before = _calls(impl="pallas", reason="bf16 call on a TPU")
    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    out, grads = run()
    # gate, up and down, traced once for the output and once under grad
    assert _calls(impl="pallas", reason="bf16 call on a TPU") - before == 6
    _assert_close(out, ref)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        _assert_close(a, b, tol=2.0 ** -4)


def test_ep_dropless_experts_give_the_same_output_on_either_side(monkeypatch):
    """Inside the EP path's `shard_map` the operands are local: the kernel
    takes the call there (the worst-case receive buffer's tail rows are the
    rows past the last group)."""
    from automodel_tpu.distributed import MeshConfig
    from automodel_tpu.moe.experts import (
        experts_forward_dropless,
        experts_forward_dropless_ep,
    )

    params, x, w, idx = _routed()
    ref = experts_forward_dropless(params, MOE, x, w, idx)
    ctx = MeshConfig(ep=2, dp_shard=4).build()
    xin = jax.device_put(x, ctx.sharding(("dp_replicate", "dp_shard", "ep", "cp"), None))
    ep = lambda: jax.jit(  # noqa: E731
        lambda p, xx: experts_forward_dropless_ep(p, MOE, xx, w, idx, ctx))(params, xin)
    _assert_close(ep(), ref)
    before = _calls(impl="pallas", reason="bf16 call on a TPU")
    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    _assert_close(ep(), ref)
    assert _calls(impl="pallas", reason="bf16 call on a TPU") - before == 3
    # a GSPMD caller on the same mesh hands its mesh over: the reference's
    before = _calls(impl="xla", reason="operands sharded under GSPMD")
    jax.eval_shape(lambda: experts_forward_dropless(params, MOE, x, w, idx, ctx))
    assert _calls(impl="xla", reason="operands sharded under GSPMD") - before == 3
