"""Benchmark recipe: steady-state step time / TPS / MFU on mock data.

The analog of the reference benchmark recipe (reference: nemo_automodel/
recipes/llm/benchmark.py — mock data, fake balanced gate, no grad clip,
the conditions of docs/performance-summary.mdx:76-83). Reuses the train
recipe's setup; the loop only times steps and reports a perf summary.
"""

from __future__ import annotations

import json
import logging
import time

import jax
import numpy as np

from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction

logger = logging.getLogger(__name__)


class BenchmarkRecipe(TrainFinetuneRecipeForNextTokenPrediction):
    def setup(self) -> None:
        # benchmark conditions: no checkpointing, no grad clip, fake gate
        self.cfg.set("checkpoint.enabled", False)
        self.cfg.set("auto_resume", False)
        if self.cfg.get("max_grad_norm", None) is None:
            self.cfg.set("max_grad_norm", None)
        if self.cfg.get("fake_balanced_gate", True):
            self.cfg.set("model.fake_balanced_gate", True)
        super().setup()

    def run_train_validation_loop(self) -> None:
        from automodel_tpu.datasets.loader import make_global_batch, stack_microbatches

        warmup = int(self.cfg.get("benchmark.warmup_steps", 2))
        times = []
        for microbatches in self.step_scheduler:
            batch_np = stack_microbatches(microbatches)
            batch = make_global_batch(
                batch_np, self.mesh_ctx, self.mesh_ctx.sharding(*self._batch_spec())
            )
            t0 = time.perf_counter()
            self.train_state, metrics = self._train_step(
                self.train_state, batch, self.rng.next_key()
            )
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            if self.step_scheduler.step > warmup:
                times.append((dt, int(batch_np["input_ids"].size) * jax.process_count()))

        if not times:
            logger.warning("benchmark ran no timed steps")
            return
        step_s = float(np.mean([t for t, _ in times]))
        tokens = times[0][1]
        perf = self.mfu.metrics(tokens, step_s)
        summary = {
            "metric": "benchmark_step_seconds",
            "steps_timed": len(times),
            "step_seconds": round(step_s, 4),
            **{k: v if v is None else round(v, 3) for k, v in perf.items()},
        }
        self.metric_logger.log(summary)
        print(json.dumps(summary))
        self.metric_logger.close()
        self.val_logger.close()
