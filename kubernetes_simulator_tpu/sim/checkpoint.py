"""Checkpoint / resume (SURVEY.md §5): snapshot the replay carry every K
chunks so a 1M-pod replay can resume after interruption; the snapshot also
doubles as a what-if fork point (snapshot → perturb → fan out).

Plain ``.npz`` — the state is four dense tensors plus a cursor; orbax would
add dependency weight for no benefit at this size. Count tensors are stored
in DOMAIN space ``[G, D]`` (the canonical semantic form — scenario-
independent); the device carry converts to and from it
(``ops.tpu3.DevState3.to_host`` / ``from_host``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class ReplayCheckpoint:
    chunk_cursor: int  # next chunk index to execute
    used: np.ndarray
    match_count: np.ndarray  # [G, D] domain space
    anti_active: np.ndarray  # [G, D]
    pref_wsum: np.ndarray  # [G, D]
    outs: List[np.ndarray]  # per-chunk collected outputs so far
    # [P] bool — pods whose completion releases are ALREADY subtracted from
    # the saved state (completions-on replays). Forking consumers must seed
    # their released mask from this or they re-subtract every pre-fork
    # release at the first post-fork boundary (advisor round-2 finding).
    # None on checkpoints written before the field existed — treated as
    # "reconstruct from outs" by the loaders that need it.
    released: Optional[np.ndarray] = None
    # Boundary-mode host-mirror state (round 5; retry/kube replays):
    # a dict of small arrays from sim.boundary.BoundaryOps.to_blob().
    # Present ⟺ the checkpoint came from a boundary-mode replay — such
    # checkpoints resume only on a matching boundary-mode engine (the
    # what-if fork path rejects them; outs are empty by design, the
    # mirror's assignments carry the placements).
    boundary: Optional[dict] = None

    def save(self, path: str) -> None:
        tmp = path + ".tmp"
        extra = {}
        if self.released is not None:
            extra["released"] = self.released.astype(bool)
        if self.boundary is not None:
            extra.update({f"bd_{k}": v for k, v in self.boundary.items()})
        np.savez_compressed(
            tmp,
            chunk_cursor=np.int64(self.chunk_cursor),
            used=self.used,
            match_count=self.match_count,
            anti_active=self.anti_active,
            pref_wsum=self.pref_wsum,
            num_outs=np.int64(len(self.outs)),
            **{f"out_{i}": o for i, o in enumerate(self.outs)},
            **extra,
        )
        os.replace(tmp + ".npz" if not tmp.endswith(".npz") else tmp, path)

    @classmethod
    def load(cls, path: str) -> "ReplayCheckpoint":
        with np.load(path) as z:
            n = int(z["num_outs"])
            bd = {
                k[len("bd_"):]: z[k] for k in z.files if k.startswith("bd_")
            }
            return cls(
                chunk_cursor=int(z["chunk_cursor"]),
                used=z["used"],
                match_count=z["match_count"],
                anti_active=z["anti_active"],
                pref_wsum=z["pref_wsum"],
                outs=[z[f"out_{i}"] for i in range(n)],
                released=z["released"] if "released" in z.files else None,
                boundary=bd or None,
            )
