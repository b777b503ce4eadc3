"""encode_readback_ms.<metric>: mean time the host waits on the copy of one
encode forward's outputs, which is where it blocks on the device: the
program's ``serve/encode/readback`` spans in the traced slice, total over
count (ServeMetrics spans)."""


def read(ctx):
    readback = ctx.serve.get("spans", {}).get("serve/encode/readback")
    if not readback or not readback["n"]:
        return None
    return readback["s"] / readback["n"] * 1e3
