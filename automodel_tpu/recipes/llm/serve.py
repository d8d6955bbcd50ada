"""Offline serving recipe: continuous-batching paged-KV generation to JSONL.

The engine-loop analog of the reference's serving benches (reference:
recipes bench_vllm/bench_sglang drive external engines; here the engine is
in-repo — serving/engine.py): load a checkpoint (or init from config), feed
the dataset's prompts through `ServingEngine.serve_batch` as a ragged
request stream with staggered arrivals, write one JSON record per request,
and log throughput/latency counters through the MetricLogger.

YAML:

    recipe: llm_serve
    model: {hf_config: {...} | pretrained_path: ...}
    dataset: {...}                    # rows provide the prompts
    serving:
      mesh:                             # typed: ServeMeshConfig (pod shape)
        replicas: 1                     # data-parallel engine replicas
        tp: 1                           # tensor parallel per replica
        ep: 1                           # expert parallel per replica (MoE)
      disaggregation:                   # typed: DisaggConfig
        enabled: false                  # split prefill/decode replica classes
        prefill_replicas: 1
        decode_replicas: 1
        transfer_pages: 8               # pages per KV-transfer program
        prefill_token_budget: null      # wider budget for the prefill class
      page_size: 16
      num_pages: 2048
      max_slots: 16
      pages_per_slot: 64              # max context = pages_per_slot * page_size
      token_budget: 64                # step rows (decode + prefill chunks)
      prefill_chunk: 48
      max_new_tokens: 64
      temperature: 0.0                # per-request; 0 → greedy
      top_k: null                     # engine-wide static filters
      top_p: null
      eos_token_id: null
      arrival_stride: 2               # admit 1 request per N engine steps
      max_prompt_len: null
      admission_policy: fifo          # fifo | prefix-hit (needs the cache)
      prefix_cache:                   # typed: PrefixCacheConfig
        enabled: false
        max_pages: null               # cap on cached pages (null → pool)
        eviction: lru                 # lru | fifo
        share_partial: true           # COW-adopt a mid-page divergence
      speculative:                    # typed: SpeculativeConfig
        enabled: false
        draft_source: ngram           # ngram only from YAML (eagle/dflash
        draft_len: 4                  #   need drafter params — API-only)
        acceptance: greedy            # greedy | sampled
        ngram_max: 3
        ngram_min: 1
      online:                         # typed: FrontendConfig (+2 recipe keys)
        enabled: false                # drive the asyncio live frontend
        deadline_steps: null          # per-request deadline (steps from
        stream_buffer: 32             #   admission; null → never shed)
        max_waiting: null
        shed_deadlines: true
      resilience:                     # typed: ServeResilienceConfig
        enabled: true                 # replica failure recovery (health
        degrade: true                 #   board + evacuate-and-requeue);
        degraded_failures: 3          #   degrade: disagg collapses to
        transfer_retry_attempts: 3    #   monolithic when prefill class
        transfer_retry_base_delay_s: 0.005   # dies (vs failing loudly)
        transfer_retry_max_delay_s: 0.25
        transfer_retry_jitter: 0.25
        retry_seed: 0
        ack_every_steps: 0            # plan-wire follower acks (0 = off)
        ack_timeout_ms: 10000
      observability:                  # typed: ObservabilityConfig
        enabled: false                # span/event tracing + flight recorder
        trace_path: null              # export prefix (null → run_dir/serve)
        flight_recorder_len: 256      # ring dumped on crash/stall
        profile_window: null          # [start_step, num_steps] jax.profiler
        itl_spike_ms: null            # ...or capture on a step-time spike
        profile_dir: null
        http_port: null               # live /metrics + /healthz (online mode)
    max_requests: 64

With `serving.online.enabled`, the SAME request stream is driven through
the asyncio online frontend (serving/frontend.py) instead of the offline
`serve_batch` host loop: requests are submitted live paced by the loop's
own step counter (`arrival_stride` becomes real admission pacing), every
generation is consumed as a token stream, and deadline-carrying requests
can be shed at admission. The mode composes with the pod shapes — a
replicated mesh serves through `OnlineRouter`, a disaggregated one
through `DisaggOnlineFrontend` (which also activates the elastic prefill
autoscaler when `disaggregation.autoscale.enabled`).
"""

from __future__ import annotations

import json
import logging
import os

import numpy as np

from automodel_tpu.config import parse_args_and_load_config
from automodel_tpu.recipes.llm.train_ft import (
    TrainFinetuneRecipeForNextTokenPrediction,
)

logger = logging.getLogger(__name__)


class ServeRecipe(TrainFinetuneRecipeForNextTokenPrediction):
    """Reuses the train chassis (model build + checkpoint load + dataloader
    + loggers); replaces the train loop with a continuous-batching serve.
    Serving holds the weights ONCE, in `model.dtype`: no fp32 masters, no
    optimizer state, no train step."""

    fp32_master_weights = False

    def setup(self) -> None:
        if self.cfg.get("checkpoint.restore_from", None):
            raise ValueError(
                "llm_serve has no train state to restore an orbax training "
                "checkpoint into; export it (checkpoint.save_consolidated) "
                "and serve it through model.pretrained_path"
            )
        self.cfg.set("checkpoint.enabled", False)
        self.cfg.set("auto_resume", False)
        super().setup()

    def _build_optimizer(self) -> None:
        """The chassis' optimizer stage, which serving does not have: keep
        the weights, build no optimizer state and no train step."""
        self.params = self._init_params
        del self._init_params

    def _requests(self, serving, serve_cfg):
        """Dataset rows → ragged Request stream (pad-stripped prompts,
        staggered arrivals). Prompts are always clamped to what the engine
        can actually hold (`pages_per_slot*page_size - max_new_tokens`) so a
        long dataset row degrades to a truncated prompt instead of blowing
        up Scheduler.submit after the model build has been paid."""
        from automodel_tpu.serving import Request

        max_requests = int(self.cfg.get("max_requests", 64))
        stride = int(serving.get("arrival_stride", 2)) if serving else 2
        max_new = int(serving.get("max_new_tokens", 64)) if serving else 64
        temp = float(serving.get("temperature", 0.0)) if serving else 0.0
        eos = serving.get("eos_token_id") if serving else None
        cap = serve_cfg.pages_per_slot * serve_cfg.page_size - max_new
        if cap < 1:
            raise ValueError(
                f"serving.max_new_tokens={max_new} leaves no room for a "
                f"prompt (max context = {cap + max_new} tokens)"
            )
        max_prompt = serving.get("max_prompt_len") if serving else None
        max_prompt = min(int(max_prompt), cap) if max_prompt else cap
        pad_id = getattr(getattr(self, "_tokenizer", None), "pad_token_id", None)

        reqs = []
        for mb in self.dataloader:
            for row in np.asarray(mb["input_ids"]).reshape(-1, np.asarray(mb["input_ids"]).shape[-1]):
                toks = [int(t) for t in row]
                if pad_id is not None:
                    while len(toks) > 1 and toks[-1] == pad_id:
                        toks.pop()
                toks = toks[:max_prompt]
                reqs.append(Request(
                    prompt=toks, max_new_tokens=max_new, temperature=temp,
                    eos_token_id=eos, seed=len(reqs),
                    arrival=len(reqs) // max(stride, 1),
                ))
                if len(reqs) >= max_requests:
                    return reqs
        return reqs

    def _serve_online(self, frontend, reqs, online_node, serve_logger):
        """Drive the asyncio frontend over the dataset's request stream:
        submissions paced by the loop's OWN step counter (each request's
        `arrival` becomes a wait_step target, so `arrival_stride` turns
        into live admission pacing), one consumer coroutine per token
        stream, optional per-request step deadlines. The frontend mutates
        the same Request objects serve_batch would, so the generations
        JSONL downstream is mode-agnostic (shed requests land there with
        finish_reason "shed"/"rejected" and no tokens)."""
        import asyncio

        deadline = online_node.get("deadline_steps")
        deadline = int(deadline) if deadline else None

        async def consume(stream):
            async for _tok in stream:
                pass

        async def drive():
            frontend.start()
            tasks = []
            for req in reqs:
                if req.arrival:
                    await frontend.wait_step(req.arrival)
                stream = frontend.submit(req, deadline_in=deadline)
                tasks.append(asyncio.ensure_future(consume(stream)))
            await asyncio.gather(*tasks)
            return await frontend.close()

        stats = asyncio.run(drive())
        serve_logger.log({"metric": "serving_online", **{
            k: v for k, v in stats.items() if np.isscalar(v)
        }})
        return {"requests": reqs, "stats": stats}

    def _build_server(self, serve_cfg):
        """The engine, or router of engines, that `serving.mesh` and
        `serving.disaggregation` ask for. It TAKES the chassis' weights:
        they arrive sharded over every visible device, each engine replica
        re-device_puts them onto its own serving mesh slice (no hop through
        host memory), and the recipe keeps no reference, so the chassis'
        copy is freed once the engines hold theirs. serving.mesh={replicas,
        tp,ep} picks the pod shape; the default 1x1x1 is the single-chip
        engine on a trivial mesh of the SAME code path."""
        from automodel_tpu.serving import (
            DisaggRouter,
            ReplicaRouter,
            ServingEngine,
        )

        params, self.params = self.params, None
        if self.peft_cfg is not None:
            from automodel_tpu.peft.lora import merge_lora

            params = merge_lora(self.base_params, params, self.peft_cfg)
            self.base_params = None
        serve_mesh = self.typed.serving_mesh
        disagg = self.typed.serving_disaggregation
        logger.info("serving with %s, mesh=%s", serve_cfg, serve_mesh)
        if disagg.enabled:
            # mesh=None → every replica meshless on the default device
            # (fused same-device transfers; the hermetic smoke mode). Any
            # non-trivial serving.mesh carves one tp*ep slice per replica
            # class member and transfers take the cross-slice split path.
            mesh_arg = (
                serve_mesh
                if serve_mesh.replicas > 1 or serve_mesh.tp > 1
                or serve_mesh.ep > 1 else None
            )
            return DisaggRouter(
                params, self.model_cfg, serve_cfg, disagg, mesh=mesh_arg,
                resilience=self.typed.serving_resilience,
            )
        if serve_mesh.replicas > 1:
            return ReplicaRouter(
                params, self.model_cfg, serve_cfg, serve_mesh,
                resilience=self.typed.serving_resilience,
            )
        return ServingEngine(
            params, self.model_cfg, serve_cfg,
            mesh_ctx=serve_mesh.build_contexts()[0],
        )

    def run_train_validation_loop(self) -> None:
        from automodel_tpu.serving import ServingConfig, ServingEngine

        cfg = self.cfg
        node = cfg.get("serving")
        get = (lambda k, d: node.get(k, d)) if node is not None else (lambda k, d: d)
        serve_cfg = ServingConfig(
            page_size=int(get("page_size", 16)),
            num_pages=int(get("num_pages", 2048)),
            max_slots=int(get("max_slots", 16)),
            pages_per_slot=int(get("pages_per_slot", 64)),
            token_budget=int(get("token_budget", 64)),
            prefill_chunk=(
                int(get("prefill_chunk", 0)) or None
            ),
            top_k=(int(get("top_k", 0)) or None),
            top_p=(float(get("top_p", 0.0)) or None),
            prefix_cache=self.typed.serving_prefix_cache,
            speculative=self.typed.serving_speculative,
            admission_policy=str(get("admission_policy", "fifo")),
            observability=self.typed.serving_observability,
            kv_cache_dtype=(get("kv_cache_dtype", None) or None),
            serve_precision=(get("serve_precision", None) or None),
        )
        reqs = self._requests(node, serve_cfg)
        # the engine (or router) stays on the recipe after the run: callers
        # read its compile-once counter and compiled step from it
        self.server = server = self._build_server(serve_cfg)
        # serving counters get their own JSONL (training.jsonl stays a
        # train-loss trail for the golden/parity tooling)
        from automodel_tpu.loggers.metric_logger import MetricLogger

        serve_logger = MetricLogger(
            os.path.join(cfg.get("run_dir", "."), "serving.jsonl")
        )
        obs = server.obs
        online_node = node.get("online") if node is not None else None
        online = (
            bool(online_node.get("enabled", False))
            if online_node is not None else False
        )
        if online:
            from automodel_tpu import serving

            frontend_cls = {
                serving.DisaggRouter: serving.DisaggOnlineFrontend,
                serving.ReplicaRouter: serving.OnlineRouter,
                ServingEngine: serving.OnlineFrontend,
            }[type(server)]
            res = self._serve_online(
                frontend_cls(server, self.typed.serving_online),
                reqs, online_node, serve_logger,
            )
        elif isinstance(server, ServingEngine):
            res = server.serve_batch(
                reqs, metric_logger=serve_logger, log_every=16,
            )
        else:
            res = server.serve_batch(reqs, metric_logger=serve_logger)
        if obs.enabled:
            # end-of-run exports: Perfetto/JSONL trace, the Prometheus
            # snapshot, and the TTFT/ITL attribution block (phase
            # components sum to the measured median TTFT by construction)
            from automodel_tpu.observability import attribution_summary

            run_dir = cfg.get("run_dir", ".")
            paths = obs.export(
                obs.cfg.trace_path or os.path.join(run_dir, "serve")
            )
            attr = attribution_summary(list(obs.tracer.events))
            res["stats"]["latency_attribution"] = attr
            prom_path = os.path.join(run_dir, "metrics.prom")
            with open(prom_path, "w") as f:
                f.write(obs.registry.snapshot_prometheus())
            serve_logger.log({
                "metric": "latency_attribution", **attr,
                "trace_paths": paths, "prometheus": prom_path,
            })
            obs.close()
        serve_logger.close()
        tokenizer = getattr(self, "_tokenizer", None)
        out_path = os.path.join(cfg.get("run_dir", "."), "generations.jsonl")
        with open(out_path, "w") as f:
            for req in res["requests"]:
                rec = {
                    "rid": req.rid,
                    "prompt_ids": list(req.prompt),
                    "generated_ids": list(req.generated),
                    "finish_reason": req.finish_reason,
                    "preemptions": req.preemptions,
                }
                if tokenizer is not None:
                    rec["text"] = tokenizer.decode(rec["generated_ids"])
                f.write(json.dumps(rec) + "\n")
        summary = {
            "metric": "serving_online" if online else "serving_decode",
            **res["stats"],
        }
        print(json.dumps(summary))
        logger.info("wrote %d generations to %s", len(res["requests"]), out_path)
        for t in self.trackers:
            t.finish()
        self.metric_logger.close()
        self.val_logger.close()


def main(argv=None) -> None:
    cfg = parse_args_and_load_config(argv)
    recipe = ServeRecipe(cfg)
    recipe.setup()
    recipe.run_train_validation_loop()


if __name__ == "__main__":
    main()
