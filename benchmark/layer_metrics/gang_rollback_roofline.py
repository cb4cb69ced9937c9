"""gang_rollback_roofline: the least time a closing wave's rollback bytes need
at the chip's HBM peak (roofline_gang.rollback_min_ms: per scenario ``used``
and the group's carried plane read and written once each) over the op time a
closing wave under ``ksim.gang_rollback`` AND ``ksim.gang_txn``, in %.

What it reads, and what it does not. The program's rollback is one masked
pass that XLA fuses with the upkeep of the group's plane, so the trace files
no op under ``ksim.gang_rollback`` alone and the time is that of both scopes:
everything the carried transaction costs in ALL waves, put down to the waves
that close a group. The plane's read and write in the other waves are in the
time and not in the bytes, so the share reads low, never high; and the state
lives in VMEM through the scan, so it is a share of a bound the pass does not
touch: how much dearer the whole transaction is than the one pass over
``used`` and the plane that a rollback has to be. With the shape fixed it
moves as 1 / ``gang_txn_ms_per_wave`` does.

The closing waves of a batch are a count of the trace, which the
configuration's file records (``counts.closing_waves`` of ``counts.waves``):
the cell is found among the ones this metric lists in BENCHMARK.json by the
shape the run reports (nodes and chunk), never by a fixed path. None where
the tree has no such scope or no listed cell has the run's shape."""

import json
from pathlib import Path

import roofline_gang
from layer_metrics import _stages

ROOT = Path(__file__).resolve().parents[2]
NAME = Path(__file__).stem


def closing_share(shape: dict):
    """closing waves / waves of the listed cell whose configuration has the
    run's nodes and chunk, or None."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = next(m for m in bench["per_layer"] if m["name"] == NAME)["workloads"]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        if cell["name"] not in listed:
            continue
        config = json.loads((ROOT / files[cell["config"]]).read_text())
        counts = config.get("counts", {})
        if (config["cluster"]["nodes"] == shape["nodes"] and "waves" in counts
                and config["engine"]["chunkWaves"] == shape["chunk_waves"]):
            return counts["closing_waves"] / counts["waves"]
    return None


def read(ctx):
    per_wave = _stages.ms_per_wave(ctx, "ksim.gang_txn", "ksim.gang_rollback")
    share = closing_share(ctx["shape"]) if per_wave else None
    if not share:
        return None
    sh = ctx["shape"]
    least = roofline_gang.rollback_min_ms(
        ctx["device_kind"], scenarios=sh["scenarios_per_chip"],
        nodes=sh["nodes"], resources=sh["resources"])
    return 100.0 * least / (per_wave / share)
