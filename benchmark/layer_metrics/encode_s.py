"""encode_s: seconds the harness spent in generate + encode (its own span
around the configuration's generator and models/encode.py)."""


def read(ctx):
    return ctx["spans"].get("encode_s")
