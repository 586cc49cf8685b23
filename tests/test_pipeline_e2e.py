"""The minimum end-to-end slice (SURVEY.md §7.3), fully live on one host:

Dataset/LLM/Hyperparameter CRs → FinetuneJob → controller launches a REAL
training subprocess (LoRA SFT, CPU) → Orbax checkpoint + completion manifest →
LLMCheckpoint CR → REAL serving subprocess answers /chat/completions → built-in
Scoring drives the endpoint → score recorded → job Successful, serving torn
down. Exercises every CRD and both process boundaries.
"""

import csv
import json
import os
import time

import pytest

from datatunerx_tpu.operator.api import (
    Dataset,
    Finetune,
    FinetuneJob,
    Hyperparameter,
    LLM,
    LLMCheckpoint,
    ObjectMeta,
    Scoring,
)
from datatunerx_tpu.operator.backends import LocalProcessBackend
from datatunerx_tpu.operator.manager import build_manager
from datatunerx_tpu.operator.store import ObjectStore
from datatunerx_tpu.serving.local_backend import LocalServingBackend

CPU_ENV = {"JAX_PLATFORMS": "cpu"}


@pytest.mark.slow
def test_minimum_end_to_end_slice(tmp_path):
    storage = str(tmp_path / "storage")
    train_csv = str(tmp_path / "train.csv")
    rows = [("what is 2+2?", "4"), ("capital of France?", "Paris"),
            ("sky color?", "blue"), ("largest planet?", "Jupiter")] * 8
    with open(train_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["q", "a"])
        w.writerows(rows)

    os.environ["STORAGE_PATH"] = storage
    store = ObjectStore()
    training = LocalProcessBackend(str(tmp_path / "jobs"), extra_env=CPU_ENV)
    serving = LocalServingBackend(str(tmp_path / "jobs"), extra_env=CPU_ENV)
    mgr = build_manager(store, training, serving, storage_path=storage,
                        with_scoring=True)

    store.create(LLM(metadata=ObjectMeta(name="m"), spec={"path": "preset:debug"}))
    store.create(Hyperparameter(
        metadata=ObjectMeta(name="hp"),
        spec={"parameters": {
            "scheduler": "constant", "optimizer": "adamw", "loRA_R": "4",
            "loRA_Alpha": "16", "loRA_Dropout": "0.0", "learningRate": "1e-2",
            "epochs": "1", "blockSize": "64", "batchSize": "4",
            "gradAccSteps": "1", "PEFT": "true",
        }},
    ))
    store.create(Dataset(
        metadata=ObjectMeta(name="ds"),
        spec={"datasetMetadata": {"datasetInfo": {
            "subsets": [{"splits": {"train": {"file": train_csv}}}],
            "features": [
                {"name": "instruction", "mapTo": "q"},
                {"name": "response", "mapTo": "a"},
            ],
        }}},
    ))
    job = FinetuneJob(metadata=ObjectMeta(name="e2e"), spec={
        "finetune": {
            "name": "e2e-finetune",
            "finetuneSpec": {
                "llm": "m", "dataset": "ds",
                "hyperparameter": {"hyperparameterRef": "hp"},
                "image": {"name": "local", "path": "preset:debug"},
                "node": 1,
            },
        },
    })
    store.create(job)

    deadline = time.time() + 600
    state = ""
    while time.time() < deadline:
        mgr.drain_scheduled(horizon_s=120, max_wall_s=60)
        state = store.get(FinetuneJob, "e2e").status.get("state")
        if state in (FinetuneJob.STATE_SUCCESSFUL, FinetuneJob.STATE_FAILED):
            break
        time.sleep(0.2)

    ft = store.try_get(Finetune, "e2e-finetune")
    job = store.get(FinetuneJob, "e2e")
    diag = ""
    if state != FinetuneJob.STATE_SUCCESSFUL:
        diag = (
            f"job={json.dumps(job.status, default=str)[:800]}\n"
            f"ft={json.dumps(ft.status if ft else {}, default=str)[:400]}\n"
            f"trainer log:\n{training.log_tail('e2e-finetune')}\n"
        )
    assert state == FinetuneJob.STATE_SUCCESSFUL, diag

    # score recorded as a string; serving torn down after eval
    score = job.status["result"]["score"]
    assert isinstance(score, str) and float(score) >= 0.0
    assert serving.status("e2e") == "NotFound"
    # provenance chain complete
    ref = ft.status["llmCheckpoint"]["llmCheckpointRef"]
    ckpt = store.get(LLMCheckpoint, ref)
    assert os.path.isdir(ckpt.spec["checkpoint"]) or os.path.exists(ckpt.spec["checkpoint"])
    scoring = store.get(Scoring, "e2e")
    assert scoring.status["score"] == score
    assert len(scoring.status["details"]) == 5


@pytest.mark.slow
def test_concurrent_experiment_two_live_jobs(tmp_path):
    """FinetuneExperiment fan-out with TWO live training subprocesses running
    concurrently (north-star metric #2 shape: concurrent FinetuneJobs on
    shared hardware), aggregated to bestVersion."""
    from datatunerx_tpu.operator.api import FinetuneExperiment

    storage = str(tmp_path / "storage")
    train_csv = str(tmp_path / "train.csv")
    rows = [("q %d" % k, "a %d" % k) for k in range(32)]
    with open(train_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["q", "a"])
        w.writerows(rows)

    os.environ["STORAGE_PATH"] = storage
    store = ObjectStore()
    training = LocalProcessBackend(str(tmp_path / "jobs"), extra_env=CPU_ENV)
    serving = LocalServingBackend(str(tmp_path / "jobs"), extra_env=CPU_ENV)
    mgr = build_manager(store, training, serving, storage_path=storage,
                        with_scoring=True)

    store.create(LLM(metadata=ObjectMeta(name="m"), spec={"path": "preset:debug"}))
    store.create(Hyperparameter(
        metadata=ObjectMeta(name="hp"),
        spec={"parameters": {
            "scheduler": "constant", "optimizer": "adamw", "loRA_R": "4",
            "loRA_Dropout": "0.0", "learningRate": "1e-2", "epochs": "1",
            "blockSize": "64", "batchSize": "4", "PEFT": "true",
        }},
    ))
    store.create(Dataset(
        metadata=ObjectMeta(name="ds"),
        spec={"datasetMetadata": {"datasetInfo": {
            "subsets": [{"splits": {"train": {"file": train_csv}}}],
            "features": [{"name": "instruction", "mapTo": "q"},
                         {"name": "response", "mapTo": "a"}],
        }}},
    ))

    def job_entry(name, lr):
        return {"name": name, "spec": {"finetune": {
            "name": f"{name}-finetune",
            "finetuneSpec": {
                "llm": "m", "dataset": "ds",
                "hyperparameter": {"hyperparameterRef": "hp",
                                   "overrides": {"learningRate": lr}},
                "image": {"name": "local", "path": "preset:debug"},
                "node": 1,
            },
        }}}

    exp = FinetuneExperiment(
        metadata=ObjectMeta(name="exp-live"),
        spec={"finetuneJobs": [job_entry("cj1", "1e-2"), job_entry("cj2", "5e-3")]},
    )
    store.create(exp)

    deadline = time.time() + 900
    state = ""
    overlapped = False
    while time.time() < deadline:
        mgr.drain_scheduled(horizon_s=120, max_wall_s=60)
        running = [n for n in ("cj1-finetune", "cj2-finetune")
                   if training.status(n) == "Running"]
        overlapped = overlapped or len(running) == 2
        state = store.get(FinetuneExperiment, "exp-live").status.get("state", "")
        if state in ("Success", "Failed"):
            break
        time.sleep(0.2)

    exp = store.get(FinetuneExperiment, "exp-live")
    diag = json.dumps(exp.status, default=str)[:1200]
    assert state == "Success", diag + "\n" + training.log_tail("cj1-finetune")
    assert overlapped, "jobs never ran concurrently"
    best = exp.status["bestVersion"]
    assert best["hyperparameter"] == "hp"
    scores = {s["name"]: s["status"]["result"]["score"]
              for s in exp.status["jobsStatus"]}
    assert best["score"] == max(scores.values(), key=float)


@pytest.mark.slow
def test_four_concurrent_jobs_through_slice_placement(tmp_path):
    """North-star metric #2 at target width (VERDICT r2 next-round #5): a
    FinetuneExperiment of FOUR jobs over a 4-slice SlicePool, live CPU
    training backends — all four run concurrently on DISJOINT slices, each
    placement is recorded in Finetune.status and released on completion, and
    bestVersion aggregates across the sweep (reference fan-out
    finetuneexperiment_controller.go:123-152)."""
    from datatunerx_tpu.operator.api import FinetuneExperiment
    from datatunerx_tpu.operator.placement import Slice, SlicePool

    storage = str(tmp_path / "storage")
    train_csv = str(tmp_path / "train.csv")
    rows = [("q %d" % k, "a %d" % k) for k in range(32)]
    with open(train_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["q", "a"])
        w.writerows(rows)

    os.environ["STORAGE_PATH"] = storage
    store = ObjectStore()
    training = LocalProcessBackend(str(tmp_path / "jobs"), extra_env=CPU_ENV)
    serving = LocalServingBackend(str(tmp_path / "jobs"), extra_env=CPU_ENV)
    pool = SlicePool([
        Slice(f"sub{i}", topology="2x4", chips=8,
              node_selector={"cloud.google.com/gke-nodepool": f"tpu-sub{i}"})
        for i in range(4)
    ])  # a v5e-32 carved into 4 × 2x4 sub-slices (BASELINE.json configuration 2)
    mgr = build_manager(store, training, serving, storage_path=storage,
                        with_scoring=True, slice_pool=pool)

    store.create(LLM(metadata=ObjectMeta(name="m"),
                     spec={"path": "preset:debug"}))
    store.create(Hyperparameter(
        metadata=ObjectMeta(name="hp"),
        spec={"parameters": {
            "scheduler": "constant", "optimizer": "adamw", "loRA_R": "4",
            "loRA_Dropout": "0.0", "learningRate": "1e-2", "epochs": "1",
            "blockSize": "64", "batchSize": "4", "PEFT": "true",
        }},
    ))
    store.create(Dataset(
        metadata=ObjectMeta(name="ds"),
        spec={"datasetMetadata": {"datasetInfo": {
            "subsets": [{"splits": {"train": {"file": train_csv}}}],
            "features": [{"name": "instruction", "mapTo": "q"},
                         {"name": "response", "mapTo": "a"}],
        }}},
    ))

    lrs = ["1e-2", "5e-3", "2e-3", "1e-3"]
    names = [f"q{i}" for i in range(4)]

    def job_entry(name, lr):
        return {"name": name, "spec": {
            "finetune": {
                "name": f"{name}-finetune",
                "finetuneSpec": {
                    "llm": "m", "dataset": "ds",
                    "hyperparameter": {"hyperparameterRef": "hp",
                                       "overrides": {"learningRate": lr}},
                    "image": {"name": "local", "path": "preset:debug"},
                    "node": 1,
                },
            },
            # single-slot serving: 4 concurrent batched engines compiling
            # at once starves a CPU box; slot scaling is covered by
            # test_batched_engine
            "serveConfig": {"slots": 1},
        }}

    store.create(FinetuneExperiment(
        metadata=ObjectMeta(name="exp4"),
        spec={"finetuneJobs": [job_entry(n, lr)
                               for n, lr in zip(names, lrs)]},
    ))

    deadline = time.time() + 2400
    state = ""
    max_overlap = 0
    seen_placements: dict = {}
    while time.time() < deadline:
        mgr.drain_scheduled(horizon_s=120, max_wall_s=60)
        running = [n for n in names
                   if training.status(f"{n}-finetune") == "Running"]
        max_overlap = max(max_overlap, len(running))
        for n in names:
            ft = store.try_get(Finetune, f"{n}-finetune")
            placement = (ft.status.get("placement") or {}) if ft else {}
            if placement.get("name"):
                seen_placements[n] = placement["name"]
        state = store.get(FinetuneExperiment, "exp4").status.get("state", "")
        if state in ("Success", "Failed"):
            break
        time.sleep(0.2)

    exp = store.get(FinetuneExperiment, "exp4")
    diag = json.dumps(exp.status, default=str)[:1500]
    assert state == "Success", diag + "\n" + training.log_tail("q0-finetune")
    assert max_overlap == 4, (
        f"all four jobs must run concurrently (max overlap {max_overlap})")
    # disjoint placement: four jobs, four distinct sub-slices
    assert len(seen_placements) == 4 and \
        len(set(seen_placements.values())) == 4, seen_placements
    # placements released once the sweep is done
    assert pool.free_count() == 4
    best = exp.status["bestVersion"]
    scores = {s["name"]: s["status"]["result"]["score"]
              for s in exp.status["jobsStatus"]}
    assert len(scores) == 4
    assert best["score"] == max(scores.values(), key=float)
