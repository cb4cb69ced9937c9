"""compile_s: XLA backend-compile plus jaxpr->MLIR lowering seconds up to
the first timed batch, from jax.monitoring (CompileMeter). With a warm
persistent cache the compile part is the cache load; the lowering part is
paid by every process."""


def read(ctx):
    c = ctx["compile"]
    return c["compile_s"] + c["lower_s"]
