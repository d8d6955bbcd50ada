"""MoE configuration.

The analog of the reference `MoEConfig`
(reference: nemo_automodel/components/moe/config.py:26-93): routed/shared
expert counts, top-k, grouped routing, score function, aux-loss coeff,
DeepSeek-style gate-bias update, expert activation. TPU-specific addition:
`capacity_factor` — the einsum-dispatch path pads each expert to a fixed
capacity so shapes stay static under jit (the XLA-native replacement for
DeepEP's dynamic all-to-all; dropped tokens ≙ capacity overflow).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed_experts: int = 8
    # one chip's share of an expert-parallel deployment: the router scores
    # and selects over all `n_routed_experts`, this layer HOLDS the weights
    # of `n_held_experts` of them, experts `first_held_expert` and on, and
    # computes their part of the result alone; what the absent experts would
    # add is left out and no exchange stands in for it. None = all held.
    n_held_experts: Optional[int] = None
    first_held_expert: int = 0
    n_shared_experts: int = 0
    experts_per_token: int = 2  # top-k
    n_groups: int = 1           # deepseek group-limited routing
    topk_groups: int = 1
    score_func: str = "softmax"  # "softmax" | "sigmoid"
    norm_topk_prob: bool = True
    route_scale: float = 1.0
    aux_loss_coeff: float = 0.0
    gate_bias_update_speed: float = 0.0  # deepseek aux-free balancing
    # silu | geglu | quick_geglu | swigluoai are GATED (3-matrix) MLPs;
    # relu2 is NON-gated (up/down only, inner = relu(u)²) — matching the
    # reference's is_gated_activation split (moe/layers.py:46-82)
    expert_activation: str = "silu"
    expert_bias: bool = False         # gpt-oss experts carry projection biases
    swiglu_limit: float = 7.0         # swigluoai clamp (HF swiglu_limit)
    router_bias: bool = False         # gpt-oss router linear has a bias
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: Optional[int] = None
    shared_expert_gated: bool = False  # qwen3-next: sigmoid(gate(x))·shared(x)
    shared_expert_activation: str = "silu"  # nemotron: relu2 (non-gated)
    capacity_factor: float = 1.25    # static-shape dispatch headroom
    # "dropless" (default): sort + ragged grouped GEMM, ragged_all_to_all
    # under EP — exact (HF never drops tokens) and avoids the (T,E,C)
    # dispatch tensor that dominates memory at DSv3 scale (E=256).
    # "capacity": einsum dispatch with padded capacity (kept for perf
    # comparison and as the GSPMD-A2A fallback).
    dispatcher: str = "dropless"
    router_dtype: str = "float32"
    fake_balanced_gate: bool = False  # perf benchmarking (reference layers.py:126)

    def __post_init__(self):
        if self.dispatcher not in ("capacity", "dropless"):
            raise ValueError(
                f"Unknown MoE dispatcher '{self.dispatcher}' "
                "(expected 'capacity' or 'dropless')"
            )
        if self.n_held_experts is not None:
            last = self.first_held_expert + self.n_held_experts
            if not (1 <= self.n_held_experts and 0 <= self.first_held_expert
                    and last <= self.n_routed_experts):
                raise ValueError(
                    f"held experts [{self.first_held_expert}, {last}) are not "
                    f"among the {self.n_routed_experts} routed ones"
                )
            if self.dispatcher != "dropless":
                raise ValueError(
                    "a layer that holds a share of its experts needs the "
                    "dropless dispatcher (the capacity einsum is over all)"
                )
        known_acts = ("silu", "geglu", "quick_geglu", "relu2", "swigluoai")
        for field in ("expert_activation", "shared_expert_activation"):
            if getattr(self, field) not in known_acts:
                raise ValueError(
                    f"Unknown {field} '{getattr(self, field)}' (expected one of {known_acts})"
                )

    @property
    def num_held(self) -> int:
        """Experts whose weights this layer holds (all of them, or its share)."""
        if self.n_held_experts is None:
            return self.n_routed_experts
        return self.n_held_experts

    @property
    def holds_all_experts(self) -> bool:
        return self.num_held == self.n_routed_experts

    @property
    def gated_experts(self) -> bool:
        return self.expert_activation != "relu2"

    @property
    def shared_expert_is_gated(self) -> bool:
        return self.shared_expert_activation != "relu2"

    @property
    def shared_intermediate(self) -> int:
        if self.shared_expert_intermediate_size is not None:
            return self.shared_expert_intermediate_size
        return self.moe_intermediate_size * self.n_shared_experts
