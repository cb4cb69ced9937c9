"""mesh_fetch_ms_per_batch: the program's ``mesh_fetch`` span inside
``handback`` of a traced meshed batch, in ms, median over batches: the
placements' all-gather over the mesh (``jit_whatif_gather``) and the ONE
fetch from one chip. The span carries the bytes fetched as its stats
(``bytes``, what ``summary()["mesh"]["fetch_bytes"]`` counts); bytes over
time is printed on stderr, not a ledger metric. None where the tree writes
no root span or the batch ran on no mesh."""

from layer_metrics import _program_spans


def read(ctx):
    ms = _program_spans.ms_per_batch(ctx, "mesh_fetch", inside="handback")
    if ms is None:
        return None
    sizes = [e[4].get("bytes") for b in _program_spans.read(ctx)["batches"]
             for e in b["children"] if e[0] == "mesh_fetch"]
    if sizes and all(isinstance(n, int) for n in sizes):
        _program_spans.say(
            f"mesh_fetch {sizes[0]} bytes in {ms:.3f} ms: "
            f"{sizes[0] / ms / 1e6:.3f} GB/s")
    return ms
