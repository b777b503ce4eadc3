"""encode_step_ms: mean host time of the engine's encode forwards in the traced
slice, each ending in the blocking read of its output (ServeMetrics
'encode' samples: total over count)."""


def read(ctx):
    if not ctx.serve.get("encode_steps"):
        return None
    return ctx.serve["encode_mean_ms"]
