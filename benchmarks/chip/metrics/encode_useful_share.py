"""encode_useful_share.<metric>: share of the tokens the encode forwards of
the traced slice computed that belong to a request: the program's
``encode_tokens_useful`` over ``encode_tokens_computed`` (rows padded to a
power of two, each row padded to its bucket). ServeMetrics counters."""


def read(ctx):
    computed = ctx.serve.get("encode_tokens_computed", 0)
    if not computed:
        return None
    return 100.0 * ctx.serve.get("encode_tokens_useful", 0) / computed
