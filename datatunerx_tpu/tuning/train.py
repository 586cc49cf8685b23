"""Trainer entrypoint: ``python -m datatunerx_tpu.tuning.train --model_name_or_path … --train_path …``

The TPU-native replacement for the reference's Ray Train driver (reference
cmd/tuning/train.py): one identical program per TPU host — no Ray, no
per-worker init function; `jax.distributed` + GSPMD replace TorchTrainer/DDP
(SURVEY.md §7.1). Pipeline:

  parse → distributed init → load model+tokenizer → template/encode/pack →
  mesh → Trainer → (resume?) → epoch loop [train_step, log, eval, save] →
  final checkpoint + completion manifest (+ optional merged export)

Reference bug fixed here (SURVEY.md §7.5): eval loads evaluation_path, not
train_path (reference train.py:346-348 loads the train file twice).
"""

from __future__ import annotations

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from datatunerx_tpu.data import BatchIterator, CsvDataset, get_template
from datatunerx_tpu.data.prefetch import (
    HostPrefetcher,
    MetricsBuffer,
    PipelineStats,
    prefetch_batches,
)
from datatunerx_tpu.data.preprocess import preprocess_preference_records
from datatunerx_tpu.parallel.distributed import maybe_initialize_distributed
from datatunerx_tpu.parallel.mesh import make_mesh, mesh_shape_for
from datatunerx_tpu.parallel.sharding import per_device_bytes, place_batch
from datatunerx_tpu.training import TrainConfig, Trainer
from datatunerx_tpu.training.checkpoint import (
    CheckpointManager,
    export_merged_model,
    write_manifest,
)
from datatunerx_tpu.training.metrics_log import MetricsLogger
from datatunerx_tpu.tuning.parser import TrainArgs, parse_train_args
from datatunerx_tpu.utils import runtime
from datatunerx_tpu.utils.model_loader import load_model_and_tokenizer


def run(args: TrainArgs) -> dict:
    # cache placement must precede the first compile; the distributed
    # runtime must be up before the backend is first asked for its devices
    runtime.configure_compile_cache()
    dist = maybe_initialize_distributed(args.num_workers)
    is_main = dist["process_id"] == 0
    runtime.require_backend()
    if is_main:
        from datatunerx_tpu import native

        # the packer's Python fallback is silent by design; say which runs
        runtime.announce("trainer", native_packer=native.available())

    # ----- model -------------------------------------------------------
    overrides = dict(
        remat=args.remat,
        attention_impl=args.attention,
    )
    if args.rope_scaling:
        overrides.update(
            rope_scaling_type=args.rope_scaling,
            rope_scaling_factor=args.rope_scaling_factor,
        )
    if args.quantization:
        if args.quantization == "int4" and args.quantization_type == "fp4":
            raise NotImplementedError(
                "fp4 is not supported; use nf4 (the reference default, "
                "cmd/tuning/parser.py:45-47)"
            )
        if args.finetuning_type != "lora":
            raise ValueError(
                "--quantization requires finetuning_type lora "
                "(quantized base weights are frozen, as with bitsandbytes+peft)"
            )
        overrides["quantization"] = args.quantization
        overrides["quant_impl"] = args.quant_impl
    dtype = jnp.bfloat16 if args.bf16 else np.float32
    cfg, params, tokenizer = load_model_and_tokenizer(
        args.model_name_or_path, dtype=dtype, seed=args.seed,
        config_overrides=overrides,
    )

    # export-only invocation: --export_dir with no --train_path
    if args.train_path is None:
        export_merged_model(jax.device_get(params), cfg, args.export_dir)
        return {"steps": 0, "metrics": {}, "manifest": None,
                "checkpoint_dir": None, "export_dir": args.export_dir}

    if args.quantization:
        from datatunerx_tpu.ops.quant import quantize_model_params

        params = quantize_model_params(params, args.quantization)

    # ----- data --------------------------------------------------------
    template = get_template(args.template, tokenizer)
    pad_id = tokenizer.pad_token_id or 0
    if args.streaming:
        train_ds = None  # records never materialize; see iterator below
        train_examples = None
    else:
        train_ds = CsvDataset(args.train_path, columns=args.columns_map)
    if args.streaming:
        pass  # encoded lazily by StreamingBatchIterator below
    elif args.stage in ("dpo", "rm"):
        train_examples = preprocess_preference_records(
            train_ds.records, template, tokenizer,
            cutoff_len=args.block_size, columns=args.columns_map,
        )
    elif args.stage == "ppo":
        from datatunerx_tpu.data.preprocess import preprocess_prompt_records

        train_examples = preprocess_prompt_records(
            train_ds.records, template, tokenizer,
            cutoff_len=args.block_size, columns=args.columns_map,
        )
    elif args.stage == "pt":
        from datatunerx_tpu.data.preprocess import preprocess_pretrain_records

        train_examples = preprocess_pretrain_records(
            train_ds.records, tokenizer,
            cutoff_len=args.block_size, columns=args.columns_map,
        )
    else:
        train_examples = train_ds.encode(template, tokenizer,
                                         cutoff_len=args.block_size)
    if not args.streaming and not train_examples:
        raise RuntimeError("Empty dataset!")
    eval_examples = None
    eval_records = None
    if args.evaluation_path and args.stage == "ppo" and is_main:
        print("[ppo] --evaluation_path ignored: PPO's held-out signal is the "
              "reward/KL curve, not a loss over a fixed eval set", flush=True)
    if args.evaluation_path and args.stage != "ppo":
        eval_ds = CsvDataset(args.evaluation_path, columns=args.columns_map)
        if args.stage in ("dpo", "rm"):
            # preference eval: mean pairwise loss over held-out pairs
            eval_examples = preprocess_preference_records(
                eval_ds.records, template, tokenizer,
                cutoff_len=args.block_size, columns=args.columns_map,
            )
        elif args.stage == "pt":
            from datatunerx_tpu.data.preprocess import (
                preprocess_pretrain_records,
            )

            eval_examples = preprocess_pretrain_records(
                eval_ds.records, tokenizer,
                cutoff_len=args.block_size, columns=args.columns_map,
            )
        else:
            eval_records = eval_ds.records
            eval_examples = eval_ds.encode(template, tokenizer,
                                           cutoff_len=args.block_size)

    # ----- mesh --------------------------------------------------------
    n_dev = len(jax.devices())
    dims = dict(args.mesh_dims or {})
    dcn_dp = int(dims.pop("dcn", 1) or 1)  # multi-slice: dp's major dim on DCN
    shape = mesh_shape_for(
        n_dev,
        dp=dims.get("dp"),
        fsdp=dims.get("fsdp", 1 if "dp" in dims else None),
        tp=dims.get("tp", 1),
        sp=dims.get("sp", 1),
    )
    mesh = make_mesh(shape, dcn_dp=dcn_dp)
    data_par = shape[0] * shape[1]

    grad_accum = args.gradient_accumulation_steps
    if args.stage == "ppo":
        if grad_accum > 1 and is_main:
            print(f"[ppo] --gradient_accumulation_steps {grad_accum} ignored:"
                  " a PPO step already makes ppo_epochs optimization passes "
                  "per rollout batch", flush=True)
        grad_accum = 1
    global_batch = args.per_device_train_batch_size * data_par * grad_accum
    iterator_cls = BatchIterator
    if args.stage in ("dpo", "rm"):
        from datatunerx_tpu.data.loader import PreferenceBatchIterator

        iterator_cls = PreferenceBatchIterator
    elif args.stage == "ppo":
        from datatunerx_tpu.data.loader import PromptBatchIterator

        iterator_cls = PromptBatchIterator
    if args.streaming:
        from datatunerx_tpu.data.loader import (
            StreamingBatchIterator,
            StreamingCsvDataset,
        )

        it = StreamingBatchIterator(
            StreamingCsvDataset(args.train_path, columns=args.columns_map),
            template, tokenizer,
            global_batch=global_batch,
            block_size=args.block_size,
            pad_id=pad_id,
            grad_accum=grad_accum,
            buffer_size=args.shuffle_buffer,
            seed=args.seed,
            host_id=dist["process_id"],
            num_hosts=dist["num_processes"],
            stage=args.stage,
        )
        # epoch length is unknown for a stream; the loop below re-opens the
        # stream (new shuffle order) until max_steps (validated > 0) land
        total_steps = args.max_steps
        steps_per_epoch = total_steps
    else:
        it = iterator_cls(
            train_examples,
            global_batch=global_batch,
            block_size=args.block_size,
            pad_id=pad_id,
            grad_accum=grad_accum,
            seed=args.seed,
            pack=args.pack_sequences,
            host_id=dist["process_id"],
            num_hosts=dist["num_processes"],
        )
        steps_per_epoch = it.steps_per_epoch()
        if steps_per_epoch == 0:
            raise RuntimeError(
                f"dataset ({len(train_examples)} examples) smaller than one "
                f"global batch ({global_batch})"
            )
        total_steps = (
            args.max_steps if args.max_steps > 0
            else int(math.ceil(steps_per_epoch * args.num_train_epochs))
        )

    # ----- trainer -----------------------------------------------------
    tcfg = TrainConfig(
        finetuning_type=args.finetuning_type,
        lora_rank=args.lora_rank,
        lora_alpha=args.lora_alpha,
        lora_dropout=args.lora_dropout,
        lora_targets=args.lora_targets,
        num_layer_trainable=args.num_layer_trainable,
        name_module_trainable=args.name_module_trainable,
        learning_rate=args.learning_rate,
        scheduler=args.lr_scheduler_type,
        optimizer=args.optim,
        warmup_ratio=args.warmup_ratio,
        weight_decay=args.weight_decay,
        max_grad_norm=args.max_grad_norm,
        # each PPO step runs ppo_epochs optimizer updates, and the optax
        # schedule counts UPDATES — scale the horizon so the LR decays over
        # the whole run instead of finishing ppo_epochs× early
        total_steps=(total_steps * max(args.ppo_epochs, 1)
                     if args.stage == "ppo" else total_steps),
        grad_accum=grad_accum,
        neftune_alpha=args.neft_alpha,
        compute_dtype=jnp.bfloat16 if args.bf16 else None,
        stage=args.stage if args.stage in ("dpo", "rm", "ppo") else "sft",
        dpo_beta=args.dpo_beta,
    )
    if args.stage == "ppo":
        from datatunerx_tpu.training.ppo import (
            PPOConfig,
            PPOTrainer,
            load_reward_model,
        )

        reward_lora, reward_scaling = load_reward_model(
            cfg, params, args.reward_model, mesh=mesh)
        trainer = PPOTrainer(
            cfg, tcfg,
            PPOConfig(
                gen_len=args.ppo_gen_len,
                temperature=args.ppo_temperature,
                kl_coef=args.init_kl_coef,
                ppo_target=args.ppo_target,
                ppo_epochs=args.ppo_epochs,
                score_norm=args.ppo_score_norm,
            ),
            reward_lora=reward_lora,
            reward_scaling=reward_scaling,
            eos_id=tokenizer.eos_token_id,
            pad_id=pad_id,
            mesh=mesh,
        )
    else:
        trainer = Trainer(cfg, tcfg, mesh=mesh)
    state = trainer.init_state(params, jax.random.PRNGKey(args.seed))
    if is_main:
        # where the state actually landed: a mesh that silently keeps
        # everything on device 0 shows here, not in the loss
        print("[mesh] " + json.dumps({
            "shape": dict(zip(("dp", "fsdp", "tp", "sp"), shape)),
            "devices": n_dev,
            "per_device_bytes": {
                "params": per_device_bytes((state.params, state.lora)),
                "opt_state": per_device_bytes(state.opt_state),
            }}, sort_keys=True), flush=True)

    from datatunerx_tpu.utils import storage

    run_name = args.uid or os.path.basename(args.output_dir.rstrip("/")) or "run"
    ckpt_dir = storage.join(args.storage_path, run_name, "checkpoints")
    ckpt = CheckpointManager(ckpt_dir, save_interval_steps=args.save_steps)
    start_step = 0
    if args.resume and ckpt.latest_step() is not None:
        restored, start_step = ckpt.restore(state)
        if restored is not None:
            state = trainer.place_state(restored)
            if args.stage == "ppo":
                from datatunerx_tpu.training.ppo import load_controller_state

                cs = load_controller_state(ckpt_dir)
                if cs is not None:
                    trainer.kl_coef = float(cs["kl_coef"])
            if is_main:
                print(f"[resume] restored step {start_step} from {ckpt_dir}", flush=True)

    logger = MetricsLogger(
        args.output_dir, total_steps,
        metrics_export_address=args.metrics_export_address, uid=args.uid,
        # lets the once-per-run prefetch advisory suggest a concrete
        # deeper --prefetch_depth when pipe_step_wait_ms p95 says the
        # step loop is starved by the input path
        prefetch_depth=args.prefetch_depth,
    )

    # ----- loop --------------------------------------------------------
    # profiling (SURVEY.md §5.1 — the reference exposes only the Ray
    # dashboard): capture a profiler trace for steps [2, 2+N) viewable in
    # TensorBoard/XProf; the trace dir lands in the completion manifest
    trace_dir = os.path.join(args.output_dir, "trace")
    profiling = {"active": False, "done": args.profile_steps <= 0}

    step_fn = trainer.step if args.stage == "ppo" else trainer.train_step
    # pipelined input path (data/prefetch.py): host batch build in a
    # background thread, batch N+1 placed on the mesh while step N executes.
    # PPO keeps its synchronous path — its step interleaves rollout
    # generation with optimization and places prompt batches itself.
    # Streaming + in-training generative eval: the stream tokenizes inside
    # the prefetch worker while the eval encodes on the main thread, and HF
    # fast tokenizers are not thread-safe ("Already borrowed" RuntimeError
    # would kill the run mid-epoch) — so the iterator clones the tokenizer
    # per encoding thread (loader.py ensure_thread_safe_encoding) and the
    # pipeline stays on; only a non-clonable tokenizer forces the old
    # synchronous fallback. Non-streaming pipelines never tokenize in the
    # worker (examples are pre-encoded; the worker only pads/packs).
    gen_eval_in_training = (args.predict_with_generate
                            and args.generate_eval_steps > 0)
    stream_thread_safe = True
    if args.prefetch_depth > 0 and args.streaming and gen_eval_in_training:
        stream_thread_safe = it.ensure_thread_safe_encoding()
    pipelined = (args.prefetch_depth > 0 and args.stage != "ppo"
                 and not (args.streaming and gen_eval_in_training
                          and not stream_thread_safe))
    if (args.prefetch_depth > 0 and args.streaming and gen_eval_in_training
            and not stream_thread_safe and is_main):
        print("[pipeline] disabled: --streaming with in-training generative "
              "eval shares one NON-CLONABLE tokenizer across threads",
              flush=True)
    pipe_stats = PipelineStats() if pipelined else None
    accum_batches = grad_accum > 1
    # non-blocking logging: step outputs buffer on device and resolve one
    # logging interval behind (or as soon as they report ready), so a logging
    # boundary never drains the dispatch pipeline
    mbuf = MetricsBuffer(lag=1)

    def _log_resolved(resolved):
        nonlocal final_metrics
        for s_done, host in resolved:
            logger.log_train(s_done, host)
            final_metrics = host

    step = 0  # counts up through start_step (skipping those batches) on resume
    final_metrics: dict = {}
    if args.streaming:
        import itertools

        epochs = itertools.count()  # re-open the stream until max_steps land
    else:
        epochs = range(int(math.ceil(total_steps / steps_per_epoch)))
    done = False
    try:
      for epoch in epochs:
        if done:
            break
        saw_batch = False
        src = it.epoch(epoch)
        # resumed: fast-forward the data stream on HOST batches, before the
        # pipeline spins up, so skipped batches are never placed on device
        # (and never past total_steps — an already-complete run must exit in
        # O(1), not re-tokenize every skipped batch)
        exhausted = False
        while step < start_step and step < total_steps:
            try:
                next(src)
            except StopIteration:
                exhausted = True
                break
            saw_batch = True
            step += 1
        if step >= total_steps:
            done = True
            break
        host_pf: HostPrefetcher | None = None
        if exhausted:
            batches = iter(())
        elif pipelined:
            batches, host_pf = prefetch_batches(
                src,
                place_fn=lambda b: place_batch(b, mesh, accum=accum_batches),
                # a retuned depth survives epoch boundaries: the advisory's
                # live resize carries into every later epoch's prefetcher
                depth=logger.effective_prefetch_depth()
                or args.prefetch_depth,
                stats=pipe_stats,
            )
            # hand the LIVE prefetcher to the advisory so it retunes the
            # bounded queue in-run instead of only printing a flag
            logger.attach_prefetcher(host_pf)
        else:
            batches = src
        try:
            # dtxlint: hot-begin -- the step loop: one iteration per train
            # step, so any host sync here stalls the dispatch pipeline
            for batch in batches:
                saw_batch = True
                if step >= total_steps:
                    done = True
                    break
                if not profiling["done"] and not profiling["active"] and step >= start_step + 1:
                    jax.profiler.start_trace(trace_dir)
                    profiling["active"] = True
                    profiling["until"] = step + args.profile_steps
                state, metrics = step_fn(state, batch)
                step += 1
                if profiling["active"] and step >= profiling["until"]:
                    # one-shot sync when the profiler window closes, so the
                    # trace contains finished steps; not a per-step stall
                    jax.block_until_ready(metrics["loss"])  # dtxlint: disable=DTX001
                    jax.profiler.stop_trace()
                    profiling.update(active=False, done=True)
                    if is_main:
                        print(f"[profile] trace captured to {trace_dir}", flush=True)
                if is_main and (step % args.logging_steps == 0 or step == total_steps):
                    extra = {"epoch": round(step / steps_per_epoch, 3)}
                    if pipe_stats is not None:
                        extra.update(pipe_stats.snapshot())
                    mbuf.push(step, metrics, extra)
                    _log_resolved(mbuf.pop_ready())
                if args.save_steps > 0:
                    if ckpt.maybe_save(state, step) and args.stage == "ppo" \
                            and is_main:
                        from datatunerx_tpu.training.ppo import (
                            save_controller_state,
                        )

                        save_controller_state(ckpt_dir, step, trainer.kl_coef)
                if eval_examples and args.eval_steps > 0 and step % args.eval_steps == 0:
                    _run_eval(trainer, state, eval_examples, args, pad_id, logger,
                              step, is_main, dist)
                # dtxlint: hot-end -- the periodic generative eval below is
                # host-driven autoregressive decode by design (small sample,
                # main process only); its syncs are inherent, not stalls
                if (args.predict_with_generate and eval_records
                        and args.generate_eval_steps > 0
                        and step % args.generate_eval_steps == 0
                        and step < total_steps  # final step gets the full pass below
                        and dist["num_processes"] == 1 and is_main):
                    # in-training generative eval: a small sample at step
                    # intervals so rouge/bleu CURVES exist, not just a final
                    # point (reference only evaluates at the end)
                    _generative_eval_step(trainer, state, cfg, tokenizer, template,
                                          eval_records, args, logger, step,
                                          tcfg.finetuning_type)
        finally:
            if host_pf is not None:
                # stops the worker thread even when the loop exits early
                # (done, max_steps, an exception) mid-epoch
                host_pf.close()
        if (eval_examples and args.eval_steps == 0 and not done
                and step < total_steps):
            # eval_steps=0 → once per epoch (final epoch's eval happens below)
            _run_eval(trainer, state, eval_examples, args, pad_id, logger,
                      step, is_main, dist)
        if not saw_batch:  # streaming: a pass produced no full batch
            if step == 0:
                raise RuntimeError("Empty dataset!")
            break

    finally:
        # also on a crash/interrupt mid-run: resolve buffered records rather
        # than dropping up to a logging interval of already-computed metrics
        _log_resolved(mbuf.drain())
    if profiling["active"]:  # window extended past the last step
        jax.profiler.stop_trace()
        profiling.update(active=False, done=True)

    # ----- final eval / save / manifest --------------------------------
    if eval_examples:
        final_metrics.update(
            _run_eval(trainer, state, eval_examples, args, pad_id, logger,
                      step, is_main, dist)
        )
    ckpt.maybe_save(state, step, force=True)
    if args.stage == "ppo" and is_main:
        from datatunerx_tpu.training.ppo import save_controller_state

        save_controller_state(ckpt_dir, step, trainer.kl_coef)

    if args.predict_with_generate and eval_records:
        # single-host only: generation is a process-0-only loop, which would
        # touch non-addressable shards / desync collectives under multi-host
        if dist["num_processes"] > 1:
            if is_main:
                print("[generate] skipped: predict_with_generate is "
                      "single-host only for now", flush=True)
        else:
            from datatunerx_tpu.training.generate import generative_eval

            gen_lora = None
            if tcfg.finetuning_type == "lora":
                gen_lora = (state.lora, trainer.scaling)
            try:
                gen_metrics = generative_eval(
                    state.params, cfg, tokenizer, template, eval_records,
                    args.output_dir,
                    lora=gen_lora,
                    max_new_tokens=args.max_new_tokens,
                    max_examples=args.generate_examples,
                    columns=args.columns_map,
                )
            except Exception as e:  # noqa: BLE001 — never lose a finished run
                print(f"[generate] failed (run preserved): {e}", flush=True)
                gen_metrics = {}
            if gen_metrics:
                logger.log_eval(step, gen_metrics)
                final_metrics.update(gen_metrics)

    manifest_path = None
    if is_main:
        checkpoint_uri = storage.join(ckpt_dir, str(step))
        manifest_path = write_manifest(
            args.storage_path, run_name, checkpoint_uri,
            metrics=final_metrics,
            extra={
                "model": args.model_name_or_path,
                "finetuning_type": args.finetuning_type,
                # serving merges the adapter with THIS scaling (alpha/rank);
                # without it a non-default --lora_alpha run would be merged
                # at the wrong scale at serve time
                "lora_scaling": (
                    trainer.scaling if tcfg.finetuning_type == "lora" else None
                ),
                "lora_alpha": (
                    args.lora_alpha if tcfg.finetuning_type == "lora" else None
                ),
                "lora_rank": (
                    args.lora_rank if tcfg.finetuning_type == "lora" else None
                ),
                "lora_targets": (
                    list(args.lora_targets)
                    if tcfg.finetuning_type == "lora" else None
                ),
                # stage/optimizer let downstream consumers (e.g. --stage ppo
                # loading this run as its reward model) rebuild a matching
                # restore template without guessing
                "stage": args.stage,
                "optimizer": args.optim,
                "reward_model": args.reward_model,
                "template": args.template,
                "mesh": dict(zip(("dp", "fsdp", "tp", "sp"), shape)),
                "steps": step,
                "trace": trace_dir if (args.profile_steps > 0 and profiling["done"]) else None,
            },
        )
        if args.export_dir:
            lora = state.lora if tcfg.finetuning_type == "lora" else None
            export_params = jax.device_get(state.params)
            if args.quantization:
                from datatunerx_tpu.models.lora import target_dims
                from datatunerx_tpu.ops.quant import dequantize_model_params

                export_params = dequantize_model_params(
                    export_params, args.quantization,
                    dims_fn=lambda n: target_dims(cfg, n),
                )
            export_merged_model(
                export_params, cfg, args.export_dir,
                lora=jax.device_get(lora) if lora is not None else None,
                scaling=trainer.scaling,
            )
    ckpt.close()
    if is_main:
        print("[runtime] compile_cache "
              f"{json.dumps(runtime.compile_cache_stats(), sort_keys=True)}",
              flush=True)
    return {
        "steps": step,
        "metrics": final_metrics,
        "manifest": manifest_path,
        "checkpoint_dir": ckpt_dir,
    }


def _generative_eval_step(trainer, state, cfg, tokenizer, template,
                          eval_records, args, logger, step, finetuning_type):
    from datatunerx_tpu.training.generate import generative_eval

    gen_lora = (state.lora, trainer.scaling) if finetuning_type == "lora" else None
    try:
        m = generative_eval(
            state.params, cfg, tokenizer, template, eval_records,
            args.output_dir, lora=gen_lora,
            max_new_tokens=args.max_new_tokens,
            # keep interval evals cheap: a handful of examples per point
            max_examples=min(args.generate_examples, 8),
            columns=args.columns_map,
        )
    except Exception as e:  # noqa: BLE001 — never kill training for an eval
        print(f"[generate@{step}] failed (training continues): {e}", flush=True)
        return
    if m:
        logger.log_eval(step, m)


def _run_eval(trainer, state, eval_examples, args, pad_id, logger, step,
              is_main, dist):
    data_par = 1
    if trainer.mesh is not None:
        data_par = trainer.mesh.shape["dp"] * trainer.mesh.shape["fsdp"]
    iterator_cls = BatchIterator
    if args.stage in ("dpo", "rm"):
        from datatunerx_tpu.data.loader import PreferenceBatchIterator

        iterator_cls = PreferenceBatchIterator
    eval_it = iterator_cls(
        eval_examples,
        global_batch=args.per_device_eval_batch_size * data_par,
        block_size=args.block_size,
        pad_id=pad_id,
        shuffle=False,
        drop_remainder=False,  # pad the tail: every eval example counts
        host_id=dist["process_id"],
        num_hosts=dist["num_processes"],
    )
    if args.prefetch_depth > 0 and trainer.mesh is not None:
        # eval rides the same pipeline as training (ROADMAP follow-on):
        # batch N+1 builds on the host and lands on the mesh while eval_step
        # N runs — eval_step already accepts PlacedBatch, and eval examples
        # are pre-encoded so the worker never touches the tokenizer
        batches, host_pf = prefetch_batches(
            eval_it.epoch(0),
            place_fn=lambda b: place_batch(b, trainer.mesh),
            depth=args.prefetch_depth,
        )
        try:
            m = trainer.evaluate(state, batches)
        finally:
            host_pf.close()
    else:
        m = trainer.evaluate(state, ({k: jnp.asarray(v) for k, v in b.items()}
                                     for b in eval_it.epoch(0)))
    if args.stage in ("dpo", "rm"):
        # eval_loss IS the mean pairwise loss over held-out pairs; exp(loss)
        # is not a perplexity in these stages
        m.pop("perplexity", None)
    if is_main:
        logger.log_eval(step, m)
    return m


def main(argv=None):
    args = parse_train_args(argv)
    result = run(args)
    print(f"[done] {result['steps']} steps; manifest: {result['manifest']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
