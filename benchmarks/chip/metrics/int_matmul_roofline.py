"""int_matmul_roofline.<metric>: least time the chip needs for the integer
matmuls of the traced slice's useful rows (the larger of operations over
the int8 peak and bytes over HBM bandwidth), over the device time of the
Mosaic int4/int8 matmul kernels in the trace."""
from work import transformer as W

KERNELS = ("int4_matmul", "int4_matmul_fused", "int8_matmul")


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.kernel_s(KERNELS)
    rows = sum(sum(st.encode_lens) for st in ctx.steps)
    calls = ctx.serve.get("encode_steps", 0)
    if busy <= 0 or not rows or not calls:
        return None
    ops, byts = W.int_matmul_work(ctx.config, rows, calls)
    least = max(ops / ctx.peaks["int8_ops"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
