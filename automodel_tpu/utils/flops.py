"""MFU / FLOPs accounting.

The analog of the reference `AutoMFU` + flops_utils (reference:
nemo_automodel/_transformers/mfu.py:110, components/utils/flops_utils.py):
per-architecture FLOPs formulas live on the model configs
(`flops_per_token`); this module adds the device peak-FLOPs table and the
MFU/TPS computation used by recipes.
"""

from __future__ import annotations

import dataclasses

import jax

#: Dense bf16 peak TFLOP/s of one chip, keyed by a substring of
#: `device.device_kind` (lower-cased). A device that is not here has no
#: peak: `device_peak_tflops` raises, it does not guess.
PEAK_TFLOPS = {
    "tpu v4": 275.0,        # Google Cloud docs, "TPU v4"
    "tpu v5 lite": 197.0,   # v5e as JAX names it; Google Cloud docs, "TPU v5e"
    "tpu v5e": 197.0,       # Google Cloud docs, "TPU v5e"
    "tpu v5p": 459.0,       # Google Cloud docs, "TPU v5p"
    "tpu v5": 459.0,        # v5p as JAX names it ("TPU v5")
    "tpu v6 lite": 918.0,   # v6e (Trillium) as JAX names it
    "tpu v6e": 918.0,       # Google Cloud docs, "TPU v6e"
}


def device_peak_tflops(device=None) -> float:
    device = device or jax.devices()[0]
    kind = device.device_kind.lower()
    for name, peak in PEAK_TFLOPS.items():
        if name in kind:
            return peak
    raise KeyError(
        f"no peak FLOP/s on record for device_kind {device.device_kind!r}; "
        "add it to utils/flops.PEAK_TFLOPS with its source"
    )


@dataclasses.dataclass
class MFUCalculator:
    """tokens/sec + MFU from a model config's flops_per_token. On a CPU
    there is no peak to hold a run to: `mfu_pct` is None there."""

    flops_per_token: float
    num_devices: int = 1
    peak_tflops_per_device: float | None = None

    def __post_init__(self):
        if (
            self.peak_tflops_per_device is None
            and jax.devices()[0].platform != "cpu"
        ):
            self.peak_tflops_per_device = device_peak_tflops()

    def metrics(self, num_tokens: int, seconds: float) -> dict:
        tps = num_tokens / seconds
        achieved = tps * self.flops_per_token
        peak = self.peak_tflops_per_device
        return {
            "tps": tps,
            "tps_per_device": tps / self.num_devices,
            "tflops_per_device": achieved / self.num_devices / 1e12,
            "mfu_pct": (
                None if peak is None
                else 100.0 * achieved / (peak * 1e12 * self.num_devices)
            ),
        }
