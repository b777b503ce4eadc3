"""Operation and byte counts against hand counts, and the bounds of the
roofline and mfu readers."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

from chipbench.pump import Step
from work import transformer as W

METRICS = Path(__file__).resolve().parents[1] / "metrics"
# 2 layers, d 8, 2 heads of 4, d_ff 16, vocab 10; layer 1 int4, layer 0 int8
CFG = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
       "intermediate_size": 16, "vocab_size": 10, "hidden_act": "gelu",
       "num_labels": 2, "plan": {"last_k_int4": 1}}
PEAKS = {"int8_ops": 1e12, "bf16_flops": 5e11, "hbm_bytes_per_s": 1e10}


def reader(stem):
    spec = importlib.util.spec_from_file_location(
        f"m_{stem}", METRICS / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_linears_by_hand():
    lins = W.linears(CFG)
    # per layer q,k,v,o (8x8) and w1 (8x16), w2 (16x8)
    assert len(lins) == 12
    assert sum(K * N for K, N, _ in lins) == 2 * (4 * 64 + 2 * 128)
    assert [b for *_, b in lins] == [8] * 6 + [4] * 6


def test_int_matmul_work_by_hand():
    ops, byts = W.int_matmul_work(CFG, rows=3, calls=2)
    assert ops == 2 * 3 * 2 * (4 * 64 + 2 * 128)
    w8 = 4 * 64 + 2 * 128                      # int8 layer: 1 byte a weight
    w4 = w8 // 2                               # int4 layer: half a byte
    scales = 4 * (4 * 8 + 16 + 8) * 2          # f32 per output channel
    rows = 3 * ((4 * (8 + 4 * 8)) + (8 + 4 * 16) + (16 + 4 * 8)) * 2
    assert byts == 2 * (w8 + w4 + scales) + rows


def test_model_ops_by_hand():
    lin = 2 * 2 * (4 * 64 + 2 * 128)
    att = lambda c: 4 * c * 2 * 4 * 2
    assert W.encode_ops(CFG, 5) == 5 * lin + 5 * att(5) + 2 * 64 + 2 * 8 * 2


class View:
    def __init__(self, kernel_s):
        self._k = kernel_s

    def kernel_s(self, names):
        return self._k


def _ctx(kernel_s, step_s=None):
    steps = [Step(0.0, 1.0, encode_lens=[64, 64])]
    least_ops, least_b = W.int_matmul_work(CFG, 128, 1)
    least = max(least_ops / PEAKS["int8_ops"],
                least_b / PEAKS["hbm_bytes_per_s"])
    serve = {"encode_steps": 1,
             "encode_mean_ms": (step_s if step_s is not None else least) * 1e3}
    cell = SimpleNamespace(config=CFG)
    return SimpleNamespace(trace=View(kernel_s), steps=steps, serve=serve,
                           config=CFG, peaks=PEAKS, cell=cell), least


def test_roofline_is_a_share_and_silent_without_events():
    read = reader("int_matmul_roofline")
    ctx, least = _ctx(kernel_s=0.0)
    assert read(ctx) is None                 # no kernel ran: nothing, not 0
    ctx, least = _ctx(kernel_s=least)
    assert abs(read(ctx) - 100.0) < 1e-9     # at the roofline: exactly 100
    ctx, _ = _ctx(kernel_s=4 * least)
    assert abs(read(ctx) - 25.0) < 1e-9
    ctx.trace = None
    assert read(ctx) is None


def test_mfu_bounded_by_peak():
    read = reader("mfu")
    ops = 2 * W.encode_ops(CFG, 64)
    ctx, _ = _ctx(kernel_s=1.0, step_s=ops / PEAKS["int8_ops"])
    assert abs(read(ctx) - 100.0) < 1e-9
    ctx, _ = _ctx(kernel_s=1.0, step_s=10 * ops / PEAKS["int8_ops"])
    assert abs(read(ctx) - 10.0) < 1e-9
