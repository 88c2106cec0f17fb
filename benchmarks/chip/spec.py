"""Loads what a run is made of, each piece from a file of its own.

    configs/<config>.json   a model configuration: its published source, the
                            keys cut from it, and every ModelConfig field
    traffic/<mix>.json      a traffic mix: the generator kind and its numbers
    cells/<cell>.json       a cell: config, mix, security mode, gateway sizing
                            and the limits its outputs are checked against
    metrics/<metric>.py     a metric's reader: ``read(run)`` -> a number, or
                            None where the run holds nothing to read
    families/<family>.py    a model family, found by the configuration's
                            ``model.family`` (the program's own name):
        leaves(cfg)             {path: (shape, rule) or (shape, rule, dtype)}
                                of every weight leaf, path joined by '/';
                                weights.py says what a rule is
        param_count(m), weight_bytes(m), kv_bytes_per_token(m),
        decode_flops(m, contexts), prefill_flops(m, lanes),
        decode_min_bytes(m, contexts)
                                counts of the engine's calls from the
                                configuration's ``model`` block (costs.py)
    references/<name>.py    the plain reference the configuration names
                            under ``reference`` (check.py)

A reader may also ask scopes.py for a named scope nested in the engine's
own (an ``experts`` scope inside the layer loop) and for host spans of the
program beyond its SPANS, by name.

A cell's metrics are listed in BENCHMARK.json at the checkout's root.  A new
cell, mix, configuration, family or metric is a new file, and a cell,
configuration or metric a new entry there too; no code here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _read(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict            # configs/<config>.json
    mix: dict               # traffic/<mix>.json
    cell: dict              # cells/<cell>.json
    end_to_end: tuple       # BENCHMARK.json end_to_end entries of this cell
    per_layer: tuple        # BENCHMARK.json per_layer entries of this cell
    data: pathlib.Path      # directory the pieces were read from

    @property
    def security(self) -> str:
        return self.cell["security"]

    @property
    def gateway(self) -> dict:
        return self.cell["gateway"]

    @property
    def limits(self) -> dict:
        return self.cell["limits"]


def model_config(config: dict):
    """The ModelConfig a configuration file states, every field given."""
    from repro.models import config as mc
    fields = dict(config["model"])
    subs = {"moe": mc.MoEConfig, "ssm": mc.SSMConfig,
            "hybrid": mc.HybridConfig, "rwkv": mc.RWKVConfig,
            "encdec": mc.EncDecConfig}
    for key, cls in subs.items():
        if key in fields:
            fields[key] = cls(**fields[key])
    return mc.ModelConfig(**fields)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported


def load_cell(name: str, data: pathlib.Path = HERE,
              bench: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench_doc = _read(bench)
    entry = next((w for w in bench_doc["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in {bench}")
    cell = _read(data / "cells" / f"{name}.json")
    if (cell["config"], cell["traffic"]) != (entry["config"],
                                            entry["traffic"]):
        raise ValueError(f"cells/{name}.json names {cell['config']}/"
                         f"{cell['traffic']}, BENCHMARK.json "
                         f"{entry['config']}/{entry['traffic']}")
    e2e = tuple(m for m in bench_doc["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    reported = {m["name"] for m in e2e}
    layer = tuple(m for m in bench_doc["per_layer"]
                  if _applies(m, name, reported))
    return Cell(name=name,
                config=_read(data / "configs" / f"{cell['config']}.json"),
                mix=_read(data / "traffic" / f"{cell['traffic']}.json"),
                cell=cell, end_to_end=e2e, per_layer=layer, data=data)


def metric_reader(name: str, data: pathlib.Path = HERE):
    """The ``read`` function of metrics/<stem>.py, where the stem is the
    metric's name up to its first dot: ``decode_step_ms.chat`` and
    ``decode_step_ms.long`` are one quantity read for two groups of cells.
    """
    stem = name.split(".", 1)[0]
    path = data / "metrics" / f"{stem}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{stem}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {name!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, data: pathlib.Path = HERE) -> dict:
    """Published peaks of one chip of this kind; a kind not in the table is
    an error, never a default."""
    table = _read(data / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{data / 'peaks.json'}")
    return table["devices"][device_kind]


def family(name: str):
    """The module families/<name>.py: a family with no module is an error,
    never a fall-back to another family's tree or counts."""
    module = f"{__package__}.families.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise KeyError(f"no family module {HERE / 'families' / name}.py "
                       f"for model family {name!r}") from None
