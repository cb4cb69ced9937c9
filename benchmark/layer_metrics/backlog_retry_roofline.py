"""backlog_retry_roofline: the least time a boundary's retry pass needs at
the chip's HBM peak (``roofline_backlog.retry_min_ms``: ``retryBuffer /
waveWidth`` wave steps' bytes and the queue's upkeep bytes) over
``backlog_retry_ms_per_boundary``, in %. The shape's ``chunk_waves`` is the
wave steps a chunk call executes, the chunk's and the pass's: the pass's are
``buffer / wave_width`` of them, the chunk's the rest."""

import json
from pathlib import Path

import roofline_backlog
from layer_metrics import _backlog

ROOT = Path(__file__).resolve().parents[2]


def buffer_of(ctx):
    """The ``retryBuffer`` of the listed cell's configuration whose shape
    the run has (looked up through BENCHMARK.json: no fixed path)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = next(m for m in bench["per_layer"]
                  if m["name"] == "backlog_retry_roofline")["workloads"]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        if w["name"] in listed:
            config = json.loads((ROOT / files[w["config"]]).read_text())
            if config["cluster"]["nodes"] == ctx["shape"]["nodes"]:
                return int(config["engine"]["retryBuffer"])
    return None


def read(ctx):
    took = _backlog.retry_ms_per_boundary(ctx)
    buffer = buffer_of(ctx) if took else None
    if not buffer:
        return None
    sh = ctx["shape"]
    pass_waves = buffer // sh["wave_width"]
    least = roofline_backlog.retry_min_ms(
        ctx["device_kind"], scenarios=sh["scenarios_per_chip"],
        nodes=sh["nodes"], resources=sh["resources"],
        wave_width=sh["wave_width"], planes=sh["planes"], buffer=buffer,
        chunk_slots=(sh["chunk_waves"] - pass_waves) * sh["wave_width"])
    return 100.0 * least / took
