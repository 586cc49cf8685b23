"""HBM capacity accounting (VERDICT r3 #4): exact param/opt/grad byte math
via eval_shape + shard divisors, analytic activation peaks, and the
admission gate that fails provably-oversized Finetunes before submission.

These tests ARE the BASELINE.json configurations 3-4 capacity claims: if a stated
configuration stops fitting its stated hardware, they fail loudly.
"""

import pytest

from datatunerx_tpu.models import get_config
from datatunerx_tpu.operator.capacity import check_admission, resolve_model_config
from datatunerx_tpu.parallel.memory import (
    Footprint,
    check_fits,
    estimate_footprint,
    hbm_budget,
)
from datatunerx_tpu.training import TrainConfig


def _lora_cfg(**kw):
    return TrainConfig(finetuning_type="lora", lora_rank=8,
                       lora_targets=("q_proj", "v_proj"), **kw)


# ------------------------------------------------------------- components

def test_footprint_component_sanity_7b_qlora():
    """llama2-7b nf4: params ≈ 3.5 GB packed + ~0.5 GB bf16 embed/lm_head;
    adapters/opt/grads tiny; BASELINE row 2 geometry fits one v5e chip."""
    cfg = get_config("llama2-7b", quantization="int4",
                     attention_impl="flash", remat="full")
    fp = estimate_footprint(cfg, _lora_cfg(), batch=4, seq=1024)
    assert 3.3e9 < fp.params < 4.5e9, fp.gb()
    assert fp.lora < 0.1e9
    assert fp.opt_state < 0.2e9
    assert fp.grads < 0.1e9
    assert fp.total < hbm_budget("v5e"), fp.gb()


def test_quantization_shrinks_params():
    cfg16 = get_config("llama2-7b")
    cfg4 = get_config("llama2-7b", quantization="int4")
    tc = _lora_cfg()
    p16 = estimate_footprint(cfg16, tc, batch=1, seq=128).params
    p4 = estimate_footprint(cfg4, tc, batch=1, seq=128).params
    # 13.5 GB bf16 → ~3.9 GB (nf4 payload + bf16 embed/lm_head/norms)
    assert p4 < p16 * 0.35, (p4 / 1e9, p16 / 1e9)


def test_fsdp_shards_params_and_opt_state():
    cfg = get_config("mistral-7b")
    tc = TrainConfig(finetuning_type="full")
    solo = estimate_footprint(cfg, tc, batch=16, seq=1024)
    sharded = estimate_footprint(cfg, tc, batch=16, seq=1024,
                                 mesh_shape={"fsdp": 16})
    # kernels shard 16-way; norms replicate, so a bit above /16
    assert sharded.params < solo.params / 12
    assert sharded.opt_state < solo.opt_state / 12
    assert sharded.grads < solo.grads / 12
    # batch shards over fsdp too
    assert sharded.activations < solo.activations / 12


def test_remat_policy_orders_activation_memory():
    cfg_full = get_config("tinyllama-1.1b", remat="full",
                          attention_impl="flash")
    cfg_dots = get_config("tinyllama-1.1b", remat="dots",
                          attention_impl="flash")
    cfg_none = get_config("tinyllama-1.1b", remat="none",
                          attention_impl="flash")
    tc = _lora_cfg()
    a_full = estimate_footprint(cfg_full, tc, batch=8, seq=1024).activations
    a_dots = estimate_footprint(cfg_dots, tc, batch=8, seq=1024).activations
    a_none = estimate_footprint(cfg_none, tc, batch=8, seq=1024).activations
    assert a_full < a_dots < a_none


def test_grad_accum_reduces_activations_not_grads():
    cfg = get_config("tinyllama-1.1b", attention_impl="flash")
    one = estimate_footprint(cfg, _lora_cfg(grad_accum=1), batch=8, seq=1024)
    four = estimate_footprint(cfg, _lora_cfg(grad_accum=4), batch=8, seq=1024)
    assert four.activations < one.activations / 3
    assert four.grads == one.grads


# ------------------------------------------ BASELINE.json configurations 3-4

def test_baseline_mistral_7b_full_param_fits_v5e16():
    """BASELINE row 4: Mistral-7B full-parameter FSDP on v5e-16."""
    cfg = get_config("mistral-7b", attention_impl="flash", remat="full")
    tc = TrainConfig(finetuning_type="full")
    fits, fp, budget = check_fits(cfg, tc, batch=16, seq=1024,
                                  mesh_shape={"fsdp": 16})
    assert fits, (fp.gb(), budget / 1e9)


def test_baseline_qwen14b_qlora_fits_v5e1():
    """BASELINE row 5: Qwen1.5-14B nf4 QLoRA on a single v5e chip.
    batch=1: the 152k-vocab fp32 logits cast dominates; batch 2 at T1024
    exceeds 15 GB, so 1 is the stated operating point."""
    cfg = get_config("qwen1.5-14b", quantization="int4",
                     attention_impl="flash", remat="full")
    fits, fp, budget = check_fits(cfg, _lora_cfg(), batch=1, seq=1024)
    assert fits, (fp.gb(), budget / 1e9)


def test_oversized_rejected_7b_full_param_single_chip():
    """Full-parameter 7B on one v5e chip: 14.5 GB params + 29 GB adam
    moments can never fit 16 GB — the checker must say so."""
    cfg = get_config("llama2-7b", attention_impl="flash", remat="full")
    tc = TrainConfig(finetuning_type="full", optimizer="adamw")
    fits, fp, _ = check_fits(cfg, tc, batch=1, seq=512)
    assert not fits
    assert fp.params + fp.opt_state > 16e9


def test_unknown_generation_raises():
    with pytest.raises(KeyError):
        hbm_budget("v99")


# ------------------------------------------------------------- admission

_HP = {
    "loRA_R": "8", "loRA_Alpha": "32", "batchSize": "4",
    "blockSize": "1024", "PEFT": "true", "int4": "true",
    "attention": "flash",
}


def test_admission_admits_resolvable_fitting_job():
    assert check_admission("preset:llama2-7b", dict(_HP), n_chips=1) is None


def test_admission_rejects_oversized_with_breakdown():
    hp = dict(_HP, PEFT="false", int4="false")  # full-param 7B, 1 chip
    denied = check_admission("preset:llama2-7b", hp, n_chips=1)
    assert denied is not None
    reason, breakdown = denied
    assert "exceeds" in reason and "budget" in reason
    assert breakdown["total"] > 16


def test_admission_rejects_mesh_larger_than_slice():
    hp = dict(_HP, meshShape="fsdp=16")
    denied = check_admission("preset:llama2-7b", hp, n_chips=4)
    assert denied is not None
    assert "chips" in denied[0]


def test_admission_admits_unresolvable_model_path():
    assert check_admission("/models/does-not-exist", dict(_HP),
                           n_chips=1) is None


def test_admission_admits_on_garbled_numerics():
    hp = dict(_HP, batchSize="not-a-number")
    assert check_admission("preset:llama2-7b", hp, n_chips=1) is None


def test_admission_respects_meshshape_sharding():
    """Full-param 7B that cannot fit 1 chip is admitted on 16 with fsdp.
    batchSize is PER-DEVICE (--per_device_train_batch_size): 1/chip here."""
    hp = dict(_HP, PEFT="false", int4="false", meshShape="fsdp=16",
              batchSize="1")
    assert check_admission("preset:llama2-7b", hp, n_chips=16) is None


def test_admission_batch_is_per_device():
    """The same per-device batchSize must yield the same per-chip estimate
    regardless of slice width — a 4-chip dp mesh must NOT dilute it 4x."""
    hp = dict(_HP)  # qwen would be tighter, but llama2-7b is the fixture
    hp["batchSize"] = "4"
    solo = check_admission("preset:llama2-7b", hp, n_chips=1)
    wide = check_admission("preset:llama2-7b", hp, n_chips=4)
    assert solo is None and wide is None
    # and an oversized per-device batch is rejected on EVERY width
    hp["batchSize"] = "64"
    assert check_admission("preset:llama2-7b", hp, n_chips=1) is not None
    assert check_admission("preset:llama2-7b", hp, n_chips=4) is not None


def test_admission_partial_mesh_mirrors_trainer_semantics():
    """_mesh_shape_from must equal tuning/train.py:147-158 exactly:
    fsdp-only -> dp absorbs the remaining chips (admit full-param Mistral
    on 16); dp-only -> fsdp defaults to 1, which cannot tile 16 chips, so
    the job is rejected AT ADMISSION with the same error the trainer's
    mesh_shape_for would raise on-slice."""
    hp = {"PEFT": "false", "batchSize": "1", "blockSize": "1024",
          "attention": "flash", "meshShape": "fsdp=16"}
    assert check_admission("preset:mistral-7b", hp, n_chips=16) is None

    hp["meshShape"] = "dp=1"
    denied = check_admission("preset:mistral-7b", hp, n_chips=16)
    assert denied is not None and "tile" in denied[0]


def test_resolve_model_config_from_dir(tmp_path):
    import dataclasses as dc
    import json

    cfg = get_config("debug")
    (tmp_path / "config.json").write_text(json.dumps(dc.asdict(cfg)))
    got = resolve_model_config(str(tmp_path))
    assert got is not None and got.hidden_size == cfg.hidden_size


def test_footprint_total_is_sum():
    fp = Footprint(params=1, lora=2, opt_state=3, grads=4, activations=5,
                   logits=6)
    assert fp.total == 21
    assert fp.gb()["total"] == round(21 / 1e9, 3)


# -------------------------------------------- controller admission wiring

def test_finetune_controller_fails_oversized_job_at_admission(tmp_path):
    """An oversized job (full-param 7B on one host) goes STATE_FAILED with
    an admissionDenied reason + byte breakdown instead of being submitted."""
    from datatunerx_tpu.operator.api import (
        Dataset, Finetune, Hyperparameter, LLM, ObjectMeta)
    from datatunerx_tpu.operator.backends import (
        FakeServingBackend, FakeTrainingBackend)
    from datatunerx_tpu.operator.manager import build_manager
    from datatunerx_tpu.operator.store import ObjectStore

    store = ObjectStore()
    training = FakeTrainingBackend()
    mgr = build_manager(store, training, FakeServingBackend(),
                        storage_path=str(tmp_path / "storage"),
                        with_scoring=False)
    ns = "default"
    store.create(LLM(metadata=ObjectMeta(name="big", namespace=ns),
                     spec={"path": "preset:llama2-7b"}))
    store.create(Hyperparameter(
        metadata=ObjectMeta(name="hp-big", namespace=ns),
        spec={"parameters": {"PEFT": "false", "batchSize": "1",
                             "blockSize": "512", "attention": "flash"}}))
    store.create(Dataset(
        metadata=ObjectMeta(name="ds-big", namespace=ns),
        spec={"datasetMetadata": {"datasetInfo": {"subsets": [{"splits": {
            "train": {"file": "/data/train.csv"}}}]}}}))
    store.create(Finetune(metadata=ObjectMeta(name="too-big", namespace=ns),
                          spec={"llm": "big", "dataset": "ds-big",
                                "hyperparameter": {
                                    "hyperparameterRef": "hp-big"},
                                "image": {"name": "img",
                                          "path": "preset:llama2-7b"},
                                "node": 1}))
    mgr.sync_all()
    mgr.run_until_idle()
    ft = store.get(Finetune, "too-big", ns)
    assert ft.status.get("state") == Finetune.STATE_FAILED
    assert "exceeds" in ft.status.get("admissionDenied", "")
    assert ft.status.get("hbmEstimateGB", {}).get("total", 0) > 16
    assert "too-big" not in training.jobs


def test_finetune_controller_admits_fitting_job(tmp_path):
    """Same wiring, QLoRA variant that fits: submission must proceed."""
    from datatunerx_tpu.operator.api import (
        Dataset, Finetune, Hyperparameter, LLM, ObjectMeta)
    from datatunerx_tpu.operator.backends import (
        FakeServingBackend, FakeTrainingBackend)
    from datatunerx_tpu.operator.manager import build_manager
    from datatunerx_tpu.operator.store import ObjectStore

    store = ObjectStore()
    training = FakeTrainingBackend()
    mgr = build_manager(store, training, FakeServingBackend(),
                        storage_path=str(tmp_path / "storage"),
                        with_scoring=False)
    ns = "default"
    store.create(LLM(metadata=ObjectMeta(name="big", namespace=ns),
                     spec={"path": "preset:llama2-7b"}))
    store.create(Hyperparameter(
        metadata=ObjectMeta(name="hp-fit", namespace=ns),
        spec={"parameters": {"PEFT": "true", "int4": "true", "loRA_R": "8",
                             "batchSize": "4", "blockSize": "1024",
                             "attention": "flash"}}))
    store.create(Dataset(
        metadata=ObjectMeta(name="ds-big", namespace=ns),
        spec={"datasetMetadata": {"datasetInfo": {"subsets": [{"splits": {
            "train": {"file": "/data/train.csv"}}}]}}}))
    store.create(Finetune(metadata=ObjectMeta(name="fits", namespace=ns),
                          spec={"llm": "big", "dataset": "ds-big",
                                "hyperparameter": {
                                    "hyperparameterRef": "hp-fit"},
                                "image": {"name": "img",
                                          "path": "preset:llama2-7b"},
                                "node": 1}))
    mgr.sync_all()
    mgr.run_until_idle()
    ft = store.get(Finetune, "fits", ns)
    assert "admissionDenied" not in ft.status
    assert ft.status.get("state") in (Finetune.STATE_PENDING,
                                      Finetune.STATE_RUNNING)
