"""What a run is, read from files by name. Nothing in here is specific to one
configuration, traffic mix or metric: a later PR adds files and entries in
BENCHMARK.json and edits nothing that is there.

  BENCHMARK.json                 which cells exist and which metrics each reports
  workloads/<cell>.json          the deployment: engine or trainer settings, limits of `correct`
  configs/<config>.json          the published config.json, what was cut, the ModelConfig fields
  traffic/<traffic>.json         parameters of one traffic mix; `kind` names the generator
  traffic/kinds/<kind>.py        the generator and driver of that kind of traffic
  metrics/<metric>.py            one reader per per-layer metric
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# published config.json key -> ModelConfig field, for the consistency check
_PUBLISHED_TO_FIELD = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings",
}


def _read_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark file missing: {path}")
    with open(path) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import a file under benchmarks/ by path: metric and kind files are
    named after what they measure (dots, hyphens), not as Python modules."""
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark file missing: {path}")
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    workload: dict      # workloads/<name>.json
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<traffic>.json
    listed: bool        # in BENCHMARK.json (a chip cell) or a rehearsal
    end_to_end: list    # metric entries of BENCHMARK.json this cell reports
    per_layer: list

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def model_fields(self) -> dict:
        return dict(self.config["model_config"])


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics_for(entries: list, cell: str, reporting: set | None = None) -> list:
    out = []
    for m in entries:
        cells = m.get("workloads")
        if cells is not None:
            if cell in cells:
                out.append(m)
        elif reporting is None or m.get("moves") in reporting:
            out.append(m)
    return out


def check_config(config: dict) -> None:
    """The registered ModelConfig fields must be the published keys."""
    fields = config["model_config"]
    for pub, field in _PUBLISHED_TO_FIELD.items():
        if pub in config and config[pub] != fields.get(field):
            raise ValueError(
                f"config {config['name']}: published {pub}={config[pub]!r} but "
                f"model_config.{field}={fields.get(field)!r}")
    window = config.get("sliding_window")
    if config.get("use_sliding_window") is False:
        window = None
    if window != fields.get("sliding_window"):
        raise ValueError(
            f"config {config['name']}: sliding_window {window!r} != "
            f"model_config.sliding_window {fields.get('sliding_window')!r}")


def load_cell(name: str) -> Cell:
    workload = _read_json("workloads", name + ".json")
    bench = benchmark_json()
    listed = next((w for w in bench["workloads"] if w["name"] == name), None)
    if listed is not None:
        for key in ("config", "traffic", "chips"):
            if listed[key] != workload[key]:
                raise ValueError(
                    f"cell {name}: BENCHMARK.json says {key}={listed[key]!r}, "
                    f"workloads/{name}.json says {workload[key]!r}")
    elif not workload.get("rehearsal"):
        raise ValueError(
            f"cell {name} is not in BENCHMARK.json and is not marked as a rehearsal")
    config = _read_json("configs", workload["config"] + ".json")
    check_config(config)
    traffic = _read_json("traffic", workload["traffic"] + ".json")
    e2e = _metrics_for(bench["end_to_end"], name) if listed else []
    per_layer = (_metrics_for(bench["per_layer"], name, {m["name"] for m in e2e})
                 if listed else [])
    return Cell(name=name, config_name=workload["config"],
                traffic_name=workload["traffic"], chips=int(workload["chips"]),
                workload=workload, config=config, traffic=traffic,
                listed=listed is not None, end_to_end=e2e, per_layer=per_layer)


def register_preset(cell: Cell, **overrides):
    """Make the configuration reachable as ``preset:<name>`` in THIS process:
    the engine and the adapter tools take a model only by preset name or by a
    directory of weights, and a benchmark PR may not edit the program."""
    from datatunerx_tpu.models.config import PRESETS, ModelConfig

    fields = dict(cell.model_fields, name=cell.config_name, **overrides)
    cfg = ModelConfig(**fields)
    PRESETS[cell.config_name] = cfg
    return cfg


def peaks_for(device_kind: str) -> dict:
    table = _read_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(have {sorted(table)}): add its published peaks with their source")
    return table[device_kind]
