"""The ragged selective scan's dispatch rule and counter
(ops/selective_scan.py): which call the Pallas kernel takes, read from the
call alone, and `selective_scan_calls_total{impl, reason}`. The parity of
the two implementations is tests/unit/test_jamba.py's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.observability.metrics import default_registry
from automodel_tpu.ops import selective_scan as scan_ops


def _calls(**labels):
    return default_registry().counter(
        "selective_scan_calls_total", **labels).value


@dataclasses.dataclass
class _Mesh:
    num_devices: int


def _call(T=8, C=128, N=8, slots=2, state_dtype=jnp.float32):
    rng = np.random.default_rng(0)
    slot = jnp.asarray([0, 0, 0, 1] + [-1] * (T - 4), jnp.int32)
    pos = jnp.asarray([4, 5, 6, 0] + [-1] * (T - 4), jnp.int32)
    return (
        jnp.asarray(rng.normal(size=(T, C)), jnp.float32),
        jnp.asarray(np.exp(rng.normal(-3, 1, (T, C))), jnp.float32),
        -jnp.exp(jnp.asarray(rng.normal(size=(N, C)), jnp.float32)),
        jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
        jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
        jnp.asarray(rng.normal(size=(slots + 1, N, C)), state_dtype),
        scan_ops.step_runs(slot, pos, trash=slots),
    )


def test_rule_reads_the_call_alone():
    s = jax.ShapeDtypeStruct
    f32 = jnp.float32

    def reason(T=256, C=5120, N=16, dtype=f32, sharded=False):
        return scan_ops._unsupported_reason(
            s((T, C), f32), s((129, N, C), dtype), sharded)

    assert reason() is None
    # no clause on the rows: a decode step's few and a long prefill's many
    assert reason(T=8) is None and reason(T=4096) is None
    assert "GSPMD" in reason(sharded=True)
    assert "not float32" in reason(dtype=jnp.bfloat16)
    assert "128 lanes" in reason(C=5000)
    assert "8 sublanes" in reason(N=4)
    assert "bytes" in reason(T=2**17)
    # the channel block: all of C where the rows' blocks fit, else the
    # widest 128-lane divisor that does
    assert scan_ops.channel_block(256, 5120, 16) == 5120
    assert scan_ops.channel_block(512, 5120, 16) == 2560
    assert scan_ops.channel_block(4096, 5120, 16) == 512
    assert scan_ops.channel_block(2**17, 5120, 16) == 0


def test_every_call_site_is_counted_with_its_reason(monkeypatch, caplog):
    args = _call()
    ref_y, ref_state = scan_ops.ragged_selective_scan(*args, impl="xla")

    def ticks(fn, **labels):
        before = _calls(**labels)
        y, state = fn()
        # the four real rows: a pad row's y is whatever its backend leaves
        np.testing.assert_allclose(y[:4], ref_y[:4], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(state[:2], ref_state[:2], rtol=1e-5,
                                   atol=1e-5)
        return _calls(**labels) - before

    # off the TPU "auto" is the reference; one tick a traced call site
    assert ticks(lambda: scan_ops.ragged_selective_scan(*args),
                 impl="xla", reason="no TPU") == 1
    jitted = jax.jit(scan_ops.ragged_selective_scan)
    assert ticks(lambda: (jitted(*args), jitted(*args))[1],
                 impl="xla", reason="no TPU") == 1
    assert ticks(lambda: scan_ops.ragged_selective_scan(*args, impl="pallas"),
                 impl="pallas", reason="requested") == 1
    # on a TPU a qualifying call is the kernel's, any other the reference's,
    # each with the rule's words, logged the first time
    monkeypatch.setattr(scan_ops, "_on_tpu", lambda: True)
    assert ticks(lambda: scan_ops.ragged_selective_scan(*args),
                 impl="pallas", reason="float32 state on a TPU") == 1
    with caplog.at_level("WARNING", logger=scan_ops.logger.name):
        assert ticks(
            lambda: scan_ops.ragged_selective_scan(*args, mesh_ctx=_Mesh(4)),
            impl="xla", reason="operands sharded under GSPMD") == 1
    assert any("lax.scan on this TPU" in r.message for r in caplog.records)
    narrow = _call(C=64)
    before = _calls(impl="xla", reason="C=64, N=8 not multiples of 128 lanes "
                                       "and 8 sublanes")
    scan_ops.ragged_selective_scan(*narrow)
    assert _calls(impl="xla", reason="C=64, N=8 not multiples of 128 lanes "
                                     "and 8 sublanes") == before + 1
    half = _call(state_dtype=jnp.bfloat16)
    before = _calls(impl="xla", reason="state bfloat16, not float32")
    _, state = scan_ops.ragged_selective_scan(*half)
    assert state.dtype == jnp.bfloat16
    assert _calls(impl="xla", reason="state bfloat16, not float32") == before + 1
    with pytest.raises(NotImplementedError, match="GSPMD"):
        scan_ops.ragged_selective_scan(*args, impl="pallas", mesh_ctx=_Mesh(4))
    with pytest.raises(ValueError, match="Unknown"):
        scan_ops.ragged_selective_scan(*args, impl="flash")
