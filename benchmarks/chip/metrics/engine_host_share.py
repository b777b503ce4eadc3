"""engine_host_share.<metric>: share of the traced slice in which the engine
ran on the host without waiting for the device: the seconds of the
program's ``serve/step`` spans less those of its ``serve/encode/readback``
spans (where the host blocks on a forward's outputs), over the slice's
wall time (ServeMetrics spans)."""


def read(ctx):
    spans = ctx.serve.get("spans", {})
    step = spans.get("serve/step")
    readback = spans.get("serve/encode/readback")
    wall = ctx.serve.get("wall_s", 0.0)
    if step is None or readback is None or wall <= 0:
        return None
    return 100.0 * (step["s"] - readback["s"]) / wall
