"""The dispatch rule of the ops that count every call site
(`ops/grouped_matmul.py`, `ops/selective_scan.py`): a Pallas TPU kernel
beside an XLA reference, chosen at trace time from what the call shows."""

from __future__ import annotations


def resolve_counted(op: str, impl: str, unsupported: str | None, *,
                    on_tpu: bool, counter: str, taken: str, reference: str,
                    logger) -> str:
    """"pallas" or "xla" for this call site of `op`, counted.

    `impl` is the caller's: "pallas" is strict (it raises where
    `unsupported`, the reason the kernel cannot take the call, is given),
    "xla" the reference, "auto" the kernel on a TPU where the call
    qualifies. Ticks `counter{impl, reason}` on the process registry (trace
    time: once a compiled call site, not once a step); the reason of an
    "auto" call the kernel takes is `taken`. The first "auto" call a TPU
    hands to `reference` is logged."""
    if impl == "pallas":
        if unsupported is not None:
            raise NotImplementedError(
                f"{op}: impl='pallas' cannot be honoured: {unsupported}")
        choice, reason = "pallas", "requested"
    elif impl == "xla":
        choice, reason = "xla", "requested"
    elif impl != "auto":
        raise ValueError(f"Unknown {op} impl '{impl}'")
    elif not on_tpu:
        choice, reason = "xla", "no TPU"
    elif unsupported is not None:
        choice, reason = "xla", unsupported
    else:
        choice, reason = "pallas", taken
    from automodel_tpu.observability.metrics import default_registry

    ticks = default_registry().counter(counter, impl=choice, reason=reason)
    if impl == "auto" and unsupported is not None and on_tpu \
            and ticks.value == 0:
        logger.warning("%s: impl='auto' runs %s on this TPU: %s",
                       op, reference, unsupported)
    ticks.inc()
    return choice
