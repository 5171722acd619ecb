"""Metric readers, one file per metric: ``metrics/<name>.py`` defines
``read(ctx) -> float | None``. ``BENCHMARK.json`` lists the metrics and
the cells each one is reported in; the harness loads the reader by the
metric's name. A reader that finds nothing to read returns None and the
metric is left out of the result line.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclass
class Ctx:
    """What a reader may read: the cell, the run's records, the reduced
    trace (traced runs), the chip's peaks."""

    cell: dict  # the BENCHMARK.json workload entry
    cfg: dict  # configs/<config>.json
    mix: dict  # traffic/<traffic>.json
    seconds: float  # the measured window
    rec: object  # recorder.Recorder
    spans: object  # harness.Spans: chunk and prefill records
    compiles: int
    peak_bytes: int
    setup_s: float
    trace: object = None  # trace.Reduced
    peaks: dict = field(default_factory=dict)


def reader(name: str):
    path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def wanted(bench: dict, kind: str, workload: str) -> list[dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") that the
    workload reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def read_all(bench: dict, kind: str, ctx: Ctx) -> dict:
    out = {}
    for m in wanted(bench, kind, ctx.cell["name"]):
        value = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks(device_kind: str) -> dict:
    """The chip's peaks (``peaks.json``); a kind not in the table is an
    error, not a default."""
    table = json.loads((HERE.parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['devices'])}")
    return table["devices"][device_kind]
