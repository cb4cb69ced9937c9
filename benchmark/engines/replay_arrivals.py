"""The single replay of a trace without durations: ``engines/replay.py``'s
adapter (one resident ``JaxReplayEngine``; a batch is one ``replay()`` of the
whole trace, back when every pod's node is on the host), answering as a
what-if batch of ONE scenario, ``assignments`` ``[1, P]``, so that a
configuration's scenario reference judges it unchanged: scenario 0 of
``whatif_scenarios.sample`` is the base cluster. The entry point the CLI's
``run --strategy jax`` uses for ``examples/config2_full_plugins_5k.yaml``."""

from __future__ import annotations

from engines import replay


class Engine(replay.Engine):
    def __init__(self, ec, ep, config: dict, traffic: dict, chunk_waves: int):
        super().__init__(ec, ep, config, traffic, chunk_waves)
        if config["engine"].get("completions"):
            raise RuntimeError("the configuration releases pods (completions); "
                               "this adapter replays arrivals only")
        if self.engine.chunk_waves != chunk_waves:
            raise RuntimeError(
                f"the program runs a chunk of {self.engine.chunk_waves} "
                f"waves, the configuration states {chunk_waves}")

    def answers(self, result) -> dict:
        """``engines/replay.py``'s, every pod's node as one scenario's row."""
        out = super().answers(result)
        return {**out, "assignments": out["assignments"].reshape(1, -1)}
