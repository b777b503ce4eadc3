"""mfu.<metric>: operations the useful (unpadded) tokens of the traced
slice need, over the engine's summed forward time times the chip's int8
peak (the weighted layers are integer). Counted from the configuration's
shapes by work/transformer.py."""
from work import transformer as W


def read(ctx):
    ops = sum(W.encode_ops(ctx.config, n)
              for st in ctx.steps for n in st.encode_lens)
    busy = (ctx.serve.get("encode_steps", 0)
            * ctx.serve.get("encode_mean_ms", 0.0) * 1e-3)
    if ops == 0 or busy <= 0:
        return None
    return 100.0 * ops / (busy * ctx.peaks["int8_ops"])
