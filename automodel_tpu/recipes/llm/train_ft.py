"""The flagship recipe: next-token-prediction finetune / pretrain.

The analog of `TrainFinetuneRecipeForNextTokenPrediction`
(reference: nemo_automodel/recipes/llm/train_ft.py:400): YAML-driven setup
of mesh → model → optimizer → data → schedulers → checkpointing, then the
train/validation loop. The reference's imperative hot loop
(_run_train_optim_step :1085) is one jitted function here
(training/train_step.py); everything around it matches: global-token loss
normalization, grad clip, MoE gate-bias update after the step (:1164),
per-step JSONL metrics with tps/MFU (:1193-1239), checkpoint cadence,
SIGTERM checkpoint-and-exit.

YAML shape (see examples/):

    model:
      hf_config: {architectures: [LlamaForCausalLM], hidden_size: …}
      # or: pretrained_path: /path/to/hf/checkpoint (config.json + safetensors)
      dtype: bfloat16
      remat_policy: full
    distributed: {dp_shard: -1, tp: 1, cp: 1, ep: 1}
    dataset: {_target_: automodel_tpu.datasets.mock.MockDatasetConfig, …}
    dataloader: {microbatch_size: 8, grad_acc_steps: 1}
    optimizer: {name: adamw, lr: 3e-4, weight_decay: 0.1}
    lr_scheduler: {warmup_steps: 100, decay_steps: 1000, style: cosine}
    step_scheduler: {max_steps: 100, ckpt_every_steps: 50, num_epochs: 1}
    checkpoint: {enabled: true, checkpoint_dir: ckpts}
    loss: {chunk_size: 1024}
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.checkpoint import (
    HFCheckpointReader,
    get_adapter,
    save_hf_checkpoint,
)
from automodel_tpu.config import ConfigNode, parse_args_and_load_config
from automodel_tpu.datasets.loader import make_global_batch, stack_microbatches
from automodel_tpu.distributed import initialize_distributed
from automodel_tpu.loggers.metric_logger import MetricLogger, setup_logging
from automodel_tpu.loss import fused_linear_cross_entropy
from automodel_tpu.loss.utils import combine_losses
from automodel_tpu.models.registry import get_model_spec
from automodel_tpu.parallel import logical_to_shardings
from automodel_tpu.recipes.base_recipe import BaseRecipe
from automodel_tpu.training import (
    TrainStepConfig,
    init_train_state,
    make_train_step,
)
from automodel_tpu.training.rng import StatefulRNG
from automodel_tpu.training.step_scheduler import StepScheduler
from automodel_tpu.utils.flops import MFUCalculator

logger = logging.getLogger(__name__)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


def make_hidden_forward(module, model_cfg, mesh_ctx, peft_cfg=None):
    """Uniform backbone forward for recipes.

    Hides the two signature forks every recipe otherwise has to handle —
    the LoRA merge (PEFT trainable tree + frozen base) and the MoE forward
    (aux loss + expert stats) — so PEFT × MoE composes in every recipe
    instead of each one growing its own fences (the reference reaches the
    same matrix through NeMoAutoModel wrappers, reference:
    nemo_automodel/_transformers/auto_model.py).

    Returns fwd(params, ids, base_params=None, token_mask=None, **kw)
      -> (merged_params, hidden, moe_aux_or_None, extra_metrics)

    merged_params is the EFFECTIVE parameter tree (post LoRA merge) — use it
    for lm-head/embedding lookups so tied heads see the adapted weights.
    """
    is_moe = getattr(model_cfg, "moe", None) is not None

    def fwd(params, ids, *, base_params=None, token_mask=None, **kw):
        if peft_cfg is not None:
            from automodel_tpu.peft.lora import merge_lora

            params = merge_lora(base_params, params, peft_cfg)
        if is_moe:
            hidden, aux, stats = module.forward(
                params, model_cfg, ids, return_hidden=True, return_stats=True,
                mesh_ctx=mesh_ctx, token_mask=token_mask, **kw,
            )
            return params, hidden, aux, {
                "tokens_per_expert": stats["tokens_per_expert"]
            }
        hidden = module.forward(
            params, model_cfg, ids, return_hidden=True, mesh_ctx=mesh_ctx, **kw
        )
        return params, hidden, None, {}

    return fwd


def _dataclass_from_cfg(cls, node, **extra):
    """Legacy non-strict coercion (kept for recipes not yet on the typed
    facade); new code should use recipes.typed_config / self.typed."""
    from automodel_tpu.recipes.typed_config import dataclass_from_node

    return dataclass_from_node(cls, node, strict=False, **extra)


class TrainFinetuneRecipeForNextTokenPrediction(BaseRecipe):
    #: the trainer holds fp32 master weights whatever `model.dtype` says;
    #: a chassis user that never takes an optimizer step (llm_serve) clears
    #: this and gets its weights in `model.dtype` from the start
    fp32_master_weights = True

    def __init__(self, cfg: ConfigNode):
        super().__init__(cfg)
        self.is_moe = False

    # ------------------------------------------------------------------
    def setup(self) -> None:
        cfg = self.cfg
        setup_logging()
        initialize_distributed()

        self.rng = StatefulRNG(seed=int(cfg.get("seed", 42)), ranked=False)
        self.mesh_ctx = self.typed.mesh.build()
        logger.info("mesh: %s", self.mesh_ctx.sizes)

        # resilience wiring comes FIRST: pretrained-weight reads in
        # _build_model already run under the remote-IO retry + fault points
        self._setup_resilience()

        self._build_model()
        self._build_optimizer()
        self._build_data()

        ckpt_cfg = dataclasses.replace(
            self.typed.checkpoint,
            save_every_steps=self.step_scheduler.config.ckpt_every_steps,
        )
        self.checkpointer = ckpt_cfg.build() if ckpt_cfg.enabled else None
        if self.checkpointer is not None and self._retry_policy is not None:
            self.checkpointer.set_retry(
                self._retry_policy, on_attempt=self._on_retry_attempt
            )

        run_dir = cfg.get("run_dir", ".")
        self.metric_logger = MetricLogger(os.path.join(run_dir, "training.jsonl"))
        self.val_logger = MetricLogger(os.path.join(run_dir, "validation.jsonl"))
        # retries that happened before the logger existed (pretrained-weight
        # reads during _build_model) surface on the first records too
        for name, n in self._retry_counts.items():
            self.metric_logger.set_counter(name, n)

        from automodel_tpu.loggers.trackers import build_trackers

        self.trackers = build_trackers(cfg, run_dir)
        for t in self.trackers:
            t.log_config(cfg.to_dict(redact=True))

        self.profiler = self.typed.profiling.build()

        seq_len = int(cfg.get("dataset.seq_len", 512))
        self.mfu = MFUCalculator(
            flops_per_token=self.model_cfg.flops_per_token(seq_len),
            num_devices=self.mesh_ctx.num_devices,
        )

        restore_from = cfg.get("checkpoint.restore_from", None)
        t_restore = time.perf_counter()
        resumed = False
        if restore_from:
            self.restore_from(restore_from, step=cfg.get("checkpoint.restore_step"))
            resumed = True
        elif cfg.get("auto_resume", True):
            try:
                resumed = self.load_checkpoint()
            except FileNotFoundError:
                pass
        if resumed:
            # time-to-resume: the goodput cost of coming back from a
            # preemption (restore only — model build/compile is the same
            # either way); surfaced on the first step's record and in the
            # bench `resilience` headline
            self._time_to_resume_s = round(time.perf_counter() - t_restore, 3)

        from automodel_tpu.training.utils import GCController

        self.gc = GCController(
            every_steps=int(cfg.get("gc_every_steps", 100)),
            enabled=bool(cfg.get("gc_control", False)),
        )
        self.step_scheduler.install_sigterm_handler()

    # ------------------------------------------------------------------
    def _setup_resilience(self) -> None:
        """Wire the fault-tolerance layer (automodel_tpu/resilience/):
        config-armed fault injection, retry-with-backoff around checkpoint +
        HF-adapter I/O, the rollback manager, and the nonfinite fail-fast
        counters. Runs BEFORE model build / checkpointer / loggers exist, so
        pretrained reads are protected too; the checkpointer wires itself in
        setup() once built. See docs/RESILIENCE.md."""
        from automodel_tpu.resilience import install_injector

        res_cfg = self.typed.resilience
        self.resilience_cfg = res_cfg
        self.fault_injector = install_injector(res_cfg.build_injector())
        if self.fault_injector.armed:
            logger.warning(
                "fault injection armed: %s",
                [dataclasses.asdict(s) for s in self.fault_injector.specs],
            )
        self._retry_policy = res_cfg.retry_policy(seed=jax.process_index())
        self._retry_counts: dict = {}
        self.rollback = res_cfg.build_rollback()
        self._nonfinite_streak = 0
        self._first_nonfinite_step: Optional[int] = None
        self._time_to_resume_s: Optional[float] = None
        self._preempt_finished = False

    def _invoke_train_step(self, batch):
        """Run the jitted train step; with `resilience.transfer_guard` the
        invocation runs under jax.transfer_guard("disallow") — the batch
        device_put above and the metric reads below stay OUTSIDE the guard,
        so the ONLY thing it can trip on is an unintended device↔host
        transfer introduced into the step path itself."""
        args = (self.train_state, batch, self.rng.next_key(), *self._step_extra())
        if self.resilience_cfg.transfer_guard:
            with jax.transfer_guard("disallow"):
                return self._train_step(*args)
        return self._train_step(*args)

    def _on_retry_attempt(self, point, attempt, exc, delay_s) -> None:
        """Every retried I/O attempt is counted through MetricLogger (once
        it exists — model-load retries are buffered and mirrored in), so
        the retry pressure a run survived is visible in training.jsonl."""
        name = f"retry_{point}"
        self._retry_counts[name] = self._retry_counts.get(name, 0) + 1
        ml = getattr(self, "metric_logger", None)
        if ml is not None:
            ml.set_counter(name, self._retry_counts[name])

    def _check_nonfinite_cap(self, step: int, nonfinite: bool) -> None:
        """Fail fast on a diverged run: without this cap,
        skip_nonfinite_updates would silently skip EVERY remaining step of
        a NaN'd run to completion (the `skipped_nonfinite` metric was
        ignored). With rollback enabled, recovery fires first; this cap is
        the backstop."""
        if not nonfinite:
            self._nonfinite_streak = 0
            self._first_nonfinite_step = None
            return
        self._nonfinite_streak += 1
        if self._first_nonfinite_step is None:
            self._first_nonfinite_step = step
        cap = int(self.resilience_cfg.max_consecutive_nonfinite or 0)
        if self.resilience_cfg.enabled and cap and self._nonfinite_streak >= cap:
            from automodel_tpu.resilience import ResilienceError

            raise ResilienceError(
                f"{self._nonfinite_streak} consecutive non-finite step(s); "
                f"first bad step: {self._first_nonfinite_step}. The run has "
                "diverged — failing fast instead of skipping every update "
                "to completion (raise resilience.max_consecutive_nonfinite "
                "or enable rollback snapshots to auto-recover)"
            )

    def _maybe_rollback(self, step: int, loss: float, nonfinite: bool) -> bool:
        """NaN/spike detection + bounded rollback. Returns True when the
        step's outcome was discarded and the loop should move on."""
        if self.rollback is None:
            return False
        reason = self.rollback.observe(step, loss, nonfinite)
        if reason is None:
            return False
        snap_step, state = self.rollback.rollback(step, reason)
        self.train_state = state
        self._nonfinite_streak = 0
        self._first_nonfinite_step = None
        # goodput counters come from the manager's stats — one source of
        # truth, mirrored into the logger so they ride every record
        self.metric_logger.set_counter("rollbacks", self.rollback.stats.rollbacks)
        self.metric_logger.set_counter("wasted_steps", self.rollback.stats.wasted_steps)
        self.metric_logger.log({
            "step": step, "event": "rollback", "reason": reason,
            "restored_step": snap_step,
        })
        return True

    def _emergency_checkpoint(self, step: int) -> None:
        """SIGTERM → forced save + grace-deadline wait for the async commit
        (preemption model: the process dies when the grace window closes)."""
        from automodel_tpu.resilience import wait_with_deadline

        t0 = time.perf_counter()
        saved = self.save_checkpoint(step, force=True)
        committed = True
        if self.checkpointer is not None:
            grace = self.step_scheduler.grace_remaining(
                float(self.resilience_cfg.sigterm_grace_s)
            )
            committed = wait_with_deadline(self.checkpointer, grace)
        seconds = round(time.perf_counter() - t0, 3)
        self.metric_logger.log({
            "step": step, "event": "emergency_checkpoint",
            "saved": bool(saved), "committed": bool(committed),
            "seconds": seconds,
        })
        if not committed:
            logger.error(
                "emergency checkpoint at step %d NOT committed within the "
                "grace window — resume will fall back to step %s",
                step,
                self.checkpointer.latest_step() if self.checkpointer else None,
            )

    # ------------------------------------------------------------------
    def _build_model(self) -> None:
        cfg = self.cfg
        mcfg = cfg.get("model")
        dtype = _DTYPES[mcfg.get("dtype", "bfloat16")]
        param_dtype = jnp.float32 if self.fp32_master_weights else dtype
        overrides = dict(
            dtype=dtype,
            remat_policy=mcfg.get("remat_policy", "full"),
            attn_impl=mcfg.get("attn_impl", "auto"),
        )
        if mcfg.get("linear_precision", None):
            overrides["linear_precision"] = mcfg.get("linear_precision")
        # DSA implementation knobs (oracle | chunked | auto; see
        # TransformerConfig.dsa_impl) — model-level YAML keys
        if mcfg.get("dsa_impl", None):
            overrides["dsa_impl"] = str(mcfg.get("dsa_impl"))
        if mcfg.get("dsa_query_block", None):
            overrides["dsa_query_block"] = int(mcfg.get("dsa_query_block"))
        # pipeline knobs live in the distributed section (reference:
        # PipelineConfig under DistributedSetup) but a model-level override
        # wins; schedule: "gpipe" (default) | "1f1b"
        dist_node = cfg.get("distributed")
        for k, conv in (
            ("pipeline_microbatches", int),
            ("pipeline_schedule", str),
            ("pipeline_virtual_stages", int),
        ):
            v = dist_node.get(k) if dist_node is not None and k in dist_node else None
            v = mcfg.get(k, v)
            if v is not None:
                overrides[k] = conv(v)
        sched = str(overrides.get("pipeline_schedule", "gpipe")).strip().lower()
        if sched in ("zbv", "zero_bubble"):
            sched = "zb"
        if sched not in ("gpipe", "1f1b", "interleaved", "zb"):
            raise ValueError(
                f"pipeline_schedule must be 'gpipe', '1f1b', 'interleaved' "
                f"or 'zb' (zero-bubble), got {overrides['pipeline_schedule']!r}"
            )
        v = int(overrides.get("pipeline_virtual_stages", 1) or 1)
        if sched == "interleaved" and v < 2:
            raise ValueError(
                "pipeline_schedule=interleaved needs pipeline_virtual_stages "
                f">= 2 (got {v}); use 1f1b for a single stage per device"
            )
        if v < 1:
            raise ValueError(f"pipeline_virtual_stages must be >= 1, got {v}")
        if "pipeline_schedule" in overrides:
            overrides["pipeline_schedule"] = sched
        # per-document CP layout (reference: distributed/blockdiag_cp/):
        # whole documents per cp rank → local attention, zero exchange
        layout = str(
            (dist_node.get("cp_layout") if dist_node is not None else None)
            or "balanced"
        ).strip().lower()
        if layout not in ("balanced", "blockdiag"):
            raise ValueError(
                f"distributed.cp_layout must be 'balanced' or 'blockdiag', got {layout!r}"
            )
        if layout == "blockdiag":
            overrides["cp_blockdiag"] = True

        pretrained = mcfg.get("pretrained_path", None)
        if pretrained:
            self._hf_reader = HFCheckpointReader(
                pretrained, retry_policy=self._retry_policy,
                on_retry=self._on_retry_attempt,
            )
            hf_config = self._hf_reader.hf_config()
        else:
            self._hf_reader = None
            hf_config = mcfg.get("hf_config")
            hf_config = hf_config.to_dict() if isinstance(hf_config, ConfigNode) else dict(hf_config)

        self.model_spec = get_model_spec(hf_config)
        self.model_cfg = self.model_spec.config_from_hf(hf_config, **overrides)
        # MoE-ness is a config property, not an adapter name: covers the MoE
        # decoder AND hybrid families (qwen3-next) whose forward returns aux
        self.is_moe = getattr(self.model_cfg, "moe", None) is not None
        if self.is_moe:
            moe_over = {}
            if cfg.get("model.fake_balanced_gate", False):
                # benchmark conditions (reference: FakeBalancedGate, layers.py:126)
                moe_over["fake_balanced_gate"] = True
            if cfg.get("model.moe_dispatcher", None):
                moe_over["dispatcher"] = cfg.get("model.moe_dispatcher")
            if moe_over:
                self.model_cfg = dataclasses.replace(
                    self.model_cfg,
                    moe=dataclasses.replace(self.model_cfg.moe, **moe_over),
                )
        self._hf_config = dict(hf_config)

        module = self.model_spec.module
        specs = module.param_specs(self.model_cfg)
        shapes = jax.eval_shape(lambda: module.init(self.model_cfg, jax.random.key(0)))
        self.param_shardings = logical_to_shardings(
            specs, self.mesh_ctx, shapes=jax.tree.map(lambda p: p.shape, shapes)
        )

        if self._hf_reader is not None:
            adapter = get_adapter(
                self.model_spec.adapter_name, self.model_cfg,
                **self.model_spec.adapter_kwargs,
            )
            params = adapter.from_hf(self._hf_reader, shardings=self.param_shardings)
            params = jax.tree.map(lambda p: jnp.asarray(p, param_dtype), params)
            if getattr(self.model_cfg, "dsa_index_topk", None) is not None:
                # V3-style checkpoints predate DSA — backfill fresh indexers
                from automodel_tpu.models.llm.mla import init_indexer

                for stack_key in ("dense_layers", "moe_layers", "layers"):
                    if stack_key in params and "indexer" not in params[stack_key]:
                        logger.warning(
                            "checkpoint carries no compatible DSA indexer "
                            "weights for %s — initializing fresh (top-k "
                            "selection starts untrained)", stack_key,
                        )
                        L_stack = jax.tree.leaves(params[stack_key])[0].shape[0]
                        params[stack_key]["indexer"] = jax.device_put(
                            init_indexer(self.model_cfg, self.rng.next_key(), L_stack),
                            self.param_shardings[stack_key]["indexer"],
                        )
            if self.is_moe and getattr(self.model_cfg, "mtp_num_layers", 0) > 0 and "mtp" not in params:
                # MTP weights are training-only and not part of HF
                # checkpoints — initialize them fresh
                from automodel_tpu.models.moe_lm.mtp import init_mtp

                params["mtp"] = jax.device_put(
                    init_mtp(self.model_cfg, self.rng.next_key()),
                    self.param_shardings["mtp"],
                )
            logger.info("loaded pretrained weights from %s", self._hf_reader._dir)
        else:
            from automodel_tpu.models.common.layers import cast_params

            # the cast is inside the jit, so no fp32 copy of the model
            # ever exists when param_dtype is narrower
            init_fn = jax.jit(
                lambda key: cast_params(
                    module.init(self.model_cfg, key), param_dtype
                ),
                out_shardings=self.param_shardings,
            )
            params = init_fn(self.rng.next_key())

        # -- PEFT / LoRA (reference: _peft/lora.py; PEFT-only checkpoints) --
        peft_node = cfg.get("peft")
        self.peft_cfg = None
        self.base_params = None
        if peft_node is not None:
            from automodel_tpu.peft.lora import init_lora, lora_param_shardings

            self.peft_cfg = self.typed.peft
            lora = init_lora(params, self.peft_cfg, self.rng.next_key())
            if self.peft_cfg.quantize_base:
                from automodel_tpu.peft.lora import quantize_base

                params = quantize_base(params, self.peft_cfg)
                logger.info("QLoRA: base weights stored %s", self.peft_cfg.quantize_base)
            self.base_params = params  # frozen, outside the optimizer
            lora_sh = lora_param_shardings(lora, self.param_shardings, self.mesh_ctx)
            params = jax.device_put(lora, lora_sh)
            n_lora = sum(p.size for p in jax.tree.leaves(params))
            logger.info("LoRA enabled: %d trainable adapter params", n_lora)
        self._init_params = params

    # ------------------------------------------------------------------
    def _build_optimizer(self) -> None:
        cfg = self.cfg
        opt_cfg = self.typed.optimizer
        sched_cfg = self.typed.lr_scheduler
        self.lr_schedule = sched_cfg.build(opt_cfg.lr)
        self.tx = opt_cfg.build(self.lr_schedule)
        state = init_train_state(self._init_params, self.tx)
        del self._init_params
        # normalize every leaf onto the mesh: params keep their NamedShardings,
        # scalars (step, adam counts) become mesh-replicated — so checkpoint
        # restore and jit see one consistent device set
        rep = self.mesh_ctx.replicated()

        def _sh(x):
            s = getattr(x, "sharding", None)
            return s if isinstance(s, jax.sharding.NamedSharding) else rep

        self.train_state = jax.device_put(state, jax.tree.map(_sh, state))
        self._install_loss(self._make_loss_fn())

    def _install_loss(self, loss_fn) -> None:
        """Jit the train/eval steps around a loss function. Single install
        point — subclasses provide the loss via _make_loss_fn()."""
        step_cfg = TrainStepConfig(
            max_grad_norm=self.cfg.get("max_grad_norm", 1.0),
            skip_nonfinite_updates=bool(self.cfg.get("skip_nonfinite_updates", False)),
        )
        # QAT: `qat: {enabled: true, precision: int8, start_step: N}`
        # (reference: quantization/qat.py + train_ft.py:861 delayed enable)
        from automodel_tpu.ops.quant import QATConfig

        qat_cfg = self.typed.qat
        if qat_cfg.enabled and self.cfg.get("peft") is not None:
            # the trainable tree is the LoRA pytree (leaves a/b/m, no
            # 'kernel'); the transform would silently fake-quant nothing.
            # Quantized-base PEFT is the QLoRA path (peft.base_precision).
            raise ValueError(
                "qat.enabled does not compose with peft (the transform only "
                "sees LoRA params); use peft.quantize_base=int8 (QLoRA) for "
                "a quantized base model instead"
            )
        grad_fn = self._make_grad_fn()
        self._train_step = jax.jit(
            make_train_step(
                loss_fn, self.tx, self.lr_schedule, step_cfg,
                param_transform=qat_cfg.make_param_transform(),
                grad_fn=grad_fn,
            ),
            donate_argnums=0,
        )

        def eval_loss(params, batch, *extra):
            loss_sum, aux = loss_fn(params, batch, jax.random.key(0), *extra)
            if not isinstance(aux, dict):
                aux = {"num_label_tokens": aux}
            return loss_sum, aux["num_label_tokens"]

        self._eval_step = jax.jit(eval_loss)

    def _make_loss_fn(self):
        cfg = self.cfg
        module = self.model_spec.module
        model_cfg = self.model_cfg
        mesh_ctx = self.mesh_ctx
        chunk = int(cfg.get("loss.chunk_size", 1024))
        is_moe = self.is_moe
        peft_cfg = self.peft_cfg

        fwd = make_hidden_forward(module, model_cfg, mesh_ctx, peft_cfg)

        def loss_fn(params, batch, rng, *extra):
            base_params = extra[0] if peft_cfg is not None else None
            kw = {}
            for k in ("positions", "segment_ids"):
                if k in batch:
                    kw[k] = batch[k]
            token_mask = (batch["labels"] != -100) if is_moe else None
            params, hidden, aux, extra = fwd(
                params, batch["input_ids"],
                base_params=base_params, token_mask=token_mask, **kw,
            )
            from automodel_tpu.models.llm.decoder import head_kernel

            kernel = head_kernel(params, model_cfg)
            ce_sum, n = fused_linear_cross_entropy(
                hidden, kernel, batch["labels"], chunk_size=chunk,
                logits_soft_cap=model_cfg.logits_soft_cap,
            )
            if is_moe and getattr(model_cfg, "mtp_num_layers", 0) > 0:
                # DeepSeek MTP auxiliary objective (reference: loss/mtp.py,
                # train_ft.py:1061) — same token normalization as the main CE
                from automodel_tpu.models.moe_lm.mtp import mtp_hidden, mtp_loss

                h_mtp = mtp_hidden(
                    params, model_cfg, hidden, batch["input_ids"],
                    kw.get("positions"), kw.get("segment_ids"),
                    lambda x, axes: x,
                )
                mtp_ce, _ = mtp_loss(
                    h_mtp, kernel, batch["labels"], chunk_size=chunk,
                    segment_ids=kw.get("segment_ids"),
                    logits_soft_cap=model_cfg.logits_soft_cap,
                )
                ce_sum = ce_sum + model_cfg.mtp_loss_coeff * mtp_ce
            total, n = combine_losses(ce_sum, n, aux)
            return total, {"num_label_tokens": n, **extra}

        return loss_fn

    def _make_grad_fn(self):
        """Explicit-gradient path: `distributed.pipeline_schedule: 1f1b`
        (or `zb` / `interleaved`) routes training through the explicit
        fwd/bwd interleave (decoder.make_pp_1f1b_loss_and_grad) instead of
        autodiff over the GPipe forward. Returns None for every other
        configuration.

        MoE decoders run the dropless expert dispatch inside each stage's
        step (ep A2A overlapped with other stages' compute); PEFT composes
        by vjp-ing the LoRA merge around the pipeline's explicit grads, and
        QAT composes the same way inside make_train_step (vjp of the
        fake-quant transform around the pipeline grads) — this path fences
        nothing."""
        if (
            self.mesh_ctx.sizes["pp"] <= 1
            or getattr(self.model_cfg, "pipeline_schedule", "gpipe")
            not in ("1f1b", "interleaved", "zb")
        ):
            return None
        from automodel_tpu.models.llm.decoder import make_pp_1f1b_loss_and_grad

        logger.info(
            "pipeline schedule: %s (explicit fwd/bwd interleave%s%s)",
            self.model_cfg.pipeline_schedule,
            ", MoE-in-pipeline" if self.is_moe else "",
            ", LoRA merge-vjp" if self.peft_cfg is not None else "",
        )
        pp_grad = make_pp_1f1b_loss_and_grad(
            self.model_cfg, self.mesh_ctx,
            chunk_size=int(self.cfg.get("loss.chunk_size", 1024)),
        )
        peft_cfg = self.peft_cfg
        if peft_cfg is None:
            return pp_grad

        from automodel_tpu.peft.lora import merge_lora

        def peft_grad_fn(lora, batch, rng, base_params):
            # d(lora) = dmerge^T · d(merged): the pipeline computes explicit
            # grads w.r.t. the merged weights; the LoRA factor grads come
            # from the vjp of the (cheap, linear-ish) merge outside the
            # pipeline shard_map.
            merged, merge_vjp = jax.vjp(
                lambda lo: merge_lora(base_params, lo, peft_cfg), lora
            )
            g_m, loss, aux = pp_grad(merged, batch, rng)
            g_m = jax.tree.map(lambda g, p: g.astype(p.dtype), g_m, merged)
            (d_lora,) = merge_vjp(g_m)
            return jax.tree.map(lambda g: g.astype(jnp.float32), d_lora), loss, aux

        return peft_grad_fn

    # ------------------------------------------------------------------
    def _build_tokenizer(self):
        """Optional `tokenizer:` section → HF tokenizer with pad defaulting
        (the NeMoAutoTokenizer analog), handed to datasets that take one."""
        node = self.cfg.get("tokenizer")
        if node is None:
            return None
        from automodel_tpu.models.auto_tokenizer import build_tokenizer

        return build_tokenizer(
            node.get("pretrained_path"),
            trust_remote_code=bool(node.get("trust_remote_code", False)),
        )

    def _build_data(self) -> None:
        cfg = self.cfg
        tokenizer = self._build_tokenizer()
        self._tokenizer = tokenizer
        ds_cfg = cfg.get("dataset").instantiate()
        try:
            dataset = ds_cfg.build(tokenizer) if tokenizer is not None else ds_cfg.build()
        except TypeError:
            dataset = ds_cfg.build()
        dl_cfg = self.typed.dataloader
        div = self.mesh_ctx.batch_size_divisor
        if dl_cfg.microbatch_size % div != 0:
            raise ValueError(
                f"dataloader.microbatch_size={dl_cfg.microbatch_size} must be "
                f"divisible by dp_replicate*dp_shard*ep={div} (the token-"
                "sharding axes of the mesh)"
            )
        self.dataloader = dl_cfg.build(dataset)
        ss_cfg = dataclasses.replace(
            self.typed.step_scheduler, grad_acc_steps=dl_cfg.grad_acc_steps
        )
        self.step_scheduler = StepScheduler(ss_cfg, self.dataloader)
        self._build_cp_sharder()

        val_node = cfg.get("validation_dataset")
        self.val_dataloader = None
        if val_node is not None:
            val_ds = val_node.instantiate().build()
            self.val_dataloader = dl_cfg.build(val_ds)

    def _build_cp_sharder(self) -> None:
        """Load-balanced CP layout (reference: context_parallel/sharder.py:116
        round-robin head/tail chunks): with causal masking an unpermuted
        sequence shard leaves cp rank 0 nearly idle while the last rank does
        ~2× the work; the permuted layout equalizes it. Applied host-side to
        every batch; positions ride the permutation, and attention is
        position-causal (ring), so the loss is unchanged (test_cp.py parity).

        Gated on the module's CP_PERMUTATION_SAFE flag — SSM/linear-attention
        hybrids and the layout-order MTP head must see natural order."""
        from automodel_tpu.parallel.cp import (
            BlockDiagContextParallelSharder,
            ContextParallelSharder,
        )

        self.cp_sharder = None
        cp = self.mesh_ctx.sizes["cp"]
        if cp <= 1:
            return
        if getattr(self.model_cfg, "cp_blockdiag", False):
            # per-document layout (blockdiag): whole docs per rank; the
            # model runs local attention (decoder.attention_block). Docs
            # stay contiguous/ordered, but the BUFFER order changes — the
            # same order-sensitivity gate as the balanced layout applies.
            if not getattr(self.model_spec.module, "CP_PERMUTATION_SAFE", False):
                raise NotImplementedError(
                    f"cp_layout=blockdiag: model {self.model_spec.name} is "
                    "sequence-order-sensitive (SSM/linear-attention buffer "
                    "order); use cp_layout: balanced with "
                    "cp_load_balanced: false"
                )
            if getattr(self.model_cfg, "mtp_num_layers", 0) > 0:
                # the MTP head shifts in LAYOUT order (moe_lm/decoder.py
                # CP_PERMUTATION_SAFE note) — a non-identity doc repack
                # would supervise wrong next-token targets
                raise NotImplementedError(
                    "cp_layout=blockdiag with MTP heads: the MTP shift is "
                    "layout-order-sensitive; use cp_layout: balanced with "
                    "cp_load_balanced: false"
                )
            if self.mesh_ctx.sizes["pp"] > 1:
                # the pipeline's manual path runs the ring regardless —
                # the configured zero-exchange layout would silently pay
                # full ring cost with an imbalanced doc-grouped layout
                raise NotImplementedError(
                    "cp_layout=blockdiag inside pipeline parallelism is not "
                    "wired (the pp path uses ring attention); use "
                    "cp_layout: balanced with pp"
                )
            self.cp_sharder = BlockDiagContextParallelSharder(cp_size=cp)
            logger.info("cp=%d: blockdiag per-document layout enabled", cp)
            return
        if not bool(self.cfg.get("distributed.cp_load_balanced", True)):
            return
        safe = getattr(self.model_spec.module, "CP_PERMUTATION_SAFE", False)
        if getattr(self.model_cfg, "mtp_num_layers", 0) > 0:
            safe = False
        if not safe:
            logger.warning(
                "cp=%d: load-balanced layout disabled — model %s is sequence-"
                "order-sensitive (SSM/MTP); causal work stays imbalanced "
                "across cp ranks", cp, self.model_spec.name,
            )
            return
        self.cp_sharder = ContextParallelSharder(cp_size=cp)
        logger.info("cp=%d: load-balanced head/tail sequence layout enabled", cp)

    # ------------------------------------------------------------------
    def _step_extra(self) -> tuple:
        return (self.base_params,) if self.peft_cfg is not None else ()

    def _batch_spec(self) -> tuple:
        return (None, "batch", "cp")  # (accum, batch, seq)

    def _make_global(self, batch_np: dict):
        if getattr(self, "cp_sharder", None) is not None:
            batch_np = self.cp_sharder.shard_batch(batch_np)
        return make_global_batch(
            batch_np, self.mesh_ctx, self.mesh_ctx.sharding(*self._batch_spec())
        )

    def _batch_token_count(self, batch_np: dict) -> int:
        """Tokens processed this step (for tps/MFU); recipes with other batch
        layouts override."""
        return int(batch_np["input_ids"].size)

    def _make_global_eval(self, batch_np: dict):
        if getattr(self, "cp_sharder", None) is not None:
            batch_np = self.cp_sharder.shard_batch(batch_np)
        return make_global_batch(
            batch_np, self.mesh_ctx, self.mesh_ctx.sharding("batch", "cp")
        )

    def run_train_validation_loop(self) -> None:
        try:
            self._run_train_validation_loop()
        except BaseException:
            # crashed runs must not look FINISHED in tracker UIs
            for t in self.trackers:
                t.finish(status="FAILED")
            self.trackers = []
            raise
        finally:
            self.gc.close()  # never leave process-wide GC disabled

    def _run_train_validation_loop(self) -> None:
        t_last = time.perf_counter()
        first_record = True
        if self.rollback is not None:
            # step-0 snapshot: a NaN on the very first steps is recoverable
            self.rollback.snapshot(self.step_scheduler.step, self.train_state)
        for microbatches in self.step_scheduler:
            step = self.step_scheduler.step
            # chaos hooks — no-ops unless armed via `resilience.faults`
            if self.fault_injector.check("sigterm", step=step) is not None:
                self.step_scheduler.sigterm_received = True
            if self.fault_injector.check("nan_grads", step=step) is not None:
                # poison the params: this step's gradients (and every later
                # step's, absent recovery) are non-finite — the scenario
                # skip_nonfinite_updates alone can never recover from
                self.train_state = self.train_state._replace(
                    params=jax.tree.map(
                        lambda p: (p * jnp.nan).astype(p.dtype),
                        self.train_state.params,
                    )
                )
            batch_np = stack_microbatches(microbatches)
            batch = self._make_global(batch_np)
            self.train_state, metrics = self._invoke_train_step(batch)
            self.profiler.step(step)
            self.gc.step(step)

            loss_val = float(metrics["loss"])
            nonfinite = (
                not np.isfinite(loss_val)
                or float(metrics.get("skipped_nonfinite", 0.0)) > 0
            )
            if self._maybe_rollback(step, loss_val, nonfinite):
                t_last = time.perf_counter()
                if self.step_scheduler.sigterm_received:
                    self._finish_preempted(step)
                    break
                continue
            self._check_nonfinite_cap(step, nonfinite)

            if self.is_moe and self.model_cfg.moe.gate_bias_update_speed > 0:
                self._update_gate_bias(metrics["tokens_per_expert"])

            now = time.perf_counter()
            n_tokens = float(metrics["num_label_tokens"])
            global_tokens = self._batch_token_count(batch_np) * jax.process_count()
            perf = self.mfu.metrics(global_tokens, now - t_last)
            t_last = now
            record = {
                "step": step,
                "epoch": self.step_scheduler.epoch,
                "loss": metrics["loss"],
                "grad_norm": metrics["grad_norm"],
                "lr": metrics.get("lr", 0.0),
                "num_label_tokens": n_tokens,
                **{k: v if v is None else round(v, 4) for k, v in perf.items()},
            }
            if "tokens_per_expert" in metrics:
                tpe = np.asarray(metrics["tokens_per_expert"])
                record["moe_load_imbalance"] = float(
                    tpe.max(-1).mean() / max(tpe.mean(), 1e-9)
                )
            # forward any extra scalar aux metrics a loss_fn reported
            for k, v in metrics.items():
                if k not in record and k != "tokens_per_expert" and getattr(v, "ndim", 0) == 0:
                    record[k] = float(v)
            if first_record and self._time_to_resume_s is not None:
                record["time_to_resume_s"] = self._time_to_resume_s
            first_record = False
            self.metric_logger.log(record)
            for t in self.trackers:
                t.log(
                    {
                        k: v for k, v in record.items()
                        if k not in ("step", "ts") and v is not None
                    },
                    step=step,
                )

            if self.rollback is not None and not nonfinite and self.rollback.due(step):
                self.rollback.snapshot(step, self.train_state)
            if self.step_scheduler.is_val_step and self.val_dataloader is not None:
                self._run_validation(step)
            if self.step_scheduler.sigterm_received:
                self._finish_preempted(step)
                break
            if self.step_scheduler.is_ckpt_step:
                self.save_checkpoint(step)

        if self.step_scheduler.sigterm_received:
            if not self._preempt_finished:
                # the signal landed AFTER the last in-loop check (e.g.
                # during the final step or its cadenced save) — run the
                # emergency path now so the grace window is still honored
                self._finish_preempted(self.step_scheduler.step)
            # preempted: the emergency path saved and waited under the
            # grace deadline — no further UNBOUNDED finalization (a
            # re-save/wait/consolidated-export here would block past the
            # grace window on exactly the commit the deadline gave up on)
            self.profiler.close()
            self.gc.close()
            self.metric_logger.close()
            self.val_logger.close()
            return
        if self.checkpointer is not None:
            self.save_checkpoint(self.step_scheduler.step, force=True)
            self.checkpointer.wait()
        if self.cfg.get("checkpoint.save_consolidated", False):
            self.save_consolidated_hf()
        self.profiler.close()
        self.gc.close()
        for t in self.trackers:
            t.finish()
        self.metric_logger.close()
        self.val_logger.close()

    def _finish_preempted(self, step: int) -> None:
        """SIGTERM path: emergency checkpoint, mark external trackers KILLED
        (reference: mlflow_utils.py), stop iterating."""
        self._preempt_finished = True
        self._emergency_checkpoint(step)
        logger.info("SIGTERM received — checkpointed and exiting")
        for t in self.trackers:
            t.finish(status="KILLED")
        self.trackers = []

    # ------------------------------------------------------------------
    def _update_gate_bias(self, tokens_per_expert) -> None:
        """DeepSeek aux-free balancing after the optimizer step
        (reference: train_ft.py:1164 update_moe_gate_bias). Stats come out
        of the train step's aux, so this costs one elementwise update.
        Modules with their own parameter layout (het_moe) export their own
        apply_gate_bias_update; the moe_lm decoder's is the default."""
        from automodel_tpu.models.moe_lm.decoder import apply_gate_bias_update

        fn = getattr(self.model_spec.module, "apply_gate_bias_update", None) or apply_gate_bias_update
        new_params = fn(
            self.train_state.params, self.model_cfg, tokens_per_expert
        )
        self.train_state = self.train_state._replace(params=new_params)

    def _run_validation(self, step: int) -> None:
        total, count = 0.0, 0.0
        for mb in self.val_dataloader:
            batch = self._make_global_eval(mb)
            loss_sum, n = self._eval_step(
                self.train_state.params, batch, *self._step_extra()
            )
            total += float(loss_sum)
            count += float(n)
        val_loss = total / max(count, 1.0)
        rec = {"step": step, "val_loss": val_loss}
        rec.update(self._run_sampling_eval())
        self.val_logger.log(rec)

    def _run_sampling_eval(self) -> dict:
        """Optional generation metrics at validation time (reference:
        components/eval DP-sharded sampling eval). Enable with

            validation_generation: {prompt_len: 16, max_new_tokens: 32,
                                    max_batches: 4}
        """
        node = self.cfg.get("validation_generation")
        if node is None or self.val_dataloader is None:
            return {}
        from automodel_tpu.models.llm import decoder as dense_decoder
        from automodel_tpu.models.moe_lm import decoder as moe_decoder_mod

        if self.model_spec.module not in (dense_decoder, moe_decoder_mod):
            logger.warning(
                "validation_generation: no KV-cache decode path for %s; skipped",
                self.model_spec.name,
            )
            return {}
        params = self.train_state.params
        if self.peft_cfg is not None:
            from automodel_tpu.peft.lora import merge_lora

            params = merge_lora(self.base_params, params, self.peft_cfg)
        # the val dataloader is resumable (its batch_index survives a
        # partial iteration); snapshot + restore so the sampling sweep
        # cannot shift the next val-loss pass's data
        dl_state = self.val_dataloader.state_dict()
        try:
            from automodel_tpu.eval.sampling import run_sampling_eval

            return run_sampling_eval(
                params, self.model_cfg, iter(self.val_dataloader),
                prompt_len=int(node.get("prompt_len", 16)),
                max_new_tokens=int(node.get("max_new_tokens", 32)),
                max_batches=int(node.get("max_batches", 4)),
                eos_token_id=node.get("eos_token_id"),
                tokenizer=getattr(self, "_tokenizer", None),
                seed=int(self.cfg.get("seed", 42)),
            )
        except NotImplementedError as e:
            logger.warning("validation_generation skipped: %s", e)
            return {}
        finally:
            self.val_dataloader.load_state_dict(dl_state)

    def save_consolidated_hf(self, out_dir: str | None = None) -> str:
        """Consolidated HF safetensors export (reference: checkpointing.py
        consolidation path)."""
        out_dir = out_dir or os.path.join(
            self.cfg.get("checkpoint.checkpoint_dir", "checkpoints"), "hf"
        )
        adapter = get_adapter(
            self.model_spec.adapter_name, self.model_cfg,
            **self.model_spec.adapter_kwargs,
        )
        if self.peft_cfg is not None:
            from automodel_tpu.peft.lora import merged_state_dict

            params = merged_state_dict(
                self.base_params, self.train_state.params, self.peft_cfg
            )
        else:
            params = jax.device_get(self.train_state.params)
        save_hf_checkpoint(
            adapter.to_hf(params), out_dir, hf_config=self._hf_config,
            retry_policy=getattr(self, "_retry_policy", None),
            on_retry=getattr(self, "_on_retry_attempt", None),
        )
        logger.info("consolidated HF checkpoint written to %s", out_dir)
        return out_dir


def main(argv=None) -> None:
    cfg = parse_args_and_load_config(argv)
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg)
    recipe.setup()
    recipe.run_train_validation_loop()


if __name__ == "__main__":
    main()
