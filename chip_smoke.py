#!/usr/bin/env python3
"""Drive W4A4 serving and QAT once on a TPU, at published widths.

    python chip_smoke.py               # one chip: phases a, b, c
    python chip_smoke.py --four-chips  # four chips: the tensor-parallel check

One process runs every phase, in order; any failure ends the run with a
non-zero exit. Weights are random, drawn from ``--seed``.

a. encoder  bert-base (L12 d768 h12 d_ff3072 vocab 30522), every layer int4,
            act_bits=4, backend='pallas', mode='encoder': 16 ``classify``
            EncodeRequests of 16-128 tokens through ServingEngine
            (prefill_batch 8), and the same requests through the same
            deployment on backend='reference'. Argmax classes must match and
            max |logit difference| must stay within ``ENCODER_LOGIT_TOL`` of
            the largest reference logit. The int4/int8 Mosaic matmuls must
            equal XLA's integer matmul exactly at the model's shapes.
b. decode   stablelm-3b (L32 d2560 d_ff6912 vocab 50304), last 16 layers
            int4, the rest int8, kv_bits=4, backend='pallas', 4 slots,
            max_len 256: 8 greedy requests (8-64-token prompts, 16 new
            tokens), and the same on backend='reference'. Held to: first-step
            logits within ``DECODE_LOGIT_TOL`` of the largest reference logit
            and the same first token for every request (unless the
            reference logits tie the two tokens within twice the measured
            logit difference); 16 tokens in every stream; exact Mosaic
            matmuls as in a; Mosaic decode attention within
            ``DECODE_ATTN_TOL`` of XLA's on the same int4/int8 cache.
            Token agreement of the streams is printed, not held: see
            "Rounding" below.
c. train    3 steps of ``run_training`` on bert-base with LSQ-MSE fake
            quantization (all layers 4-bit), batch 8, sequence 128, from a
            fresh checkpoint directory; every loss must be finite.

After a and b, the StableHLO of the engine's step programs (``jit_step``,
``jit_pf``, ``jit_cf``, ``jit_ef``), dumped by ``jax_dump_ir_to`` as each
goes to the compiler — before the persistent compile cache is consulted,
so a cache hit is still seen — must hold the Mosaic kernels
(``tpu_custom_call``) each phase runs: int4 matmul and act_quant in a; int4
and int8 matmul, act_quant and decode attention in b — so no kernel ran in
interpret mode.

Rounding. Both backends accumulate the same integer codes exactly; they
differ only where XLA fuses the float glue of the two programs differently.
A W4A4 network turns a one-ulp difference into a flipped 4-bit activation
code, and random weights amplify it layer by layer, so each phase also
prints how far the reference moves from itself when every activation scale
is nudged one float32 ulp: the yardstick for the pallas-vs-reference
difference. XLA's license to keep excess precision is switched off
(``--xla_allow_excess_precision=false``); with it on, XLA rounds the two
programs' fused glue differently and the decode prefill logits part ways.

``--four-chips`` runs only this: stablelm-3b with the phase-b mix on
backend='reference' at tp=4 and at tp=1 (tp is reference-only today) serves
one greedy burst each; the streams must be byte-identical, and the weight
bytes each device holds at tp=4 are printed against the model's total.

The last stdout line is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}``. Without a TPU the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ENCODER_LOGIT_TOL = 0.5       # x max |reference logit| (see "Rounding")
DECODE_LOGIT_TOL = 0.02       # x max |reference logit|
DECODE_ATTN_TOL = 0.02        # x max |XLA decode attention output|
KERNELS = {"int4": ("int4_matmul", "int4_matmul_fused"),
           "int8": ("int8_matmul",), "act_quant": ("act_quant",),
           "decode_attention": ("decode_attention",)}
_CUSTOM_CALL = re.compile(r'stablehlo\.custom_call @tpu_custom_call\(.*?'
                          r'kernel_name = "(\w+)"')
_ENGINE_STEP = re.compile(r"_jit_(step|pf|cf|ef)_compile\.mlir$")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends in backend compiles, read per phase."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.total += duration


def kernels_in(dump: Path, seen: set) -> set:
    """Mosaic kernel names in the engine step modules new since ``seen``."""
    found = set()
    for f in sorted(dump.glob("*.mlir")):
        if _ENGINE_STEP.search(f.name) and f.name not in seen:
            seen.add(f.name)
            found |= set(_CUSTOM_CALL.findall(f.read_text()))
    return found


def require_kernels(phase: str, found: set, needed) -> None:
    missing = [k for k in needed
               if not any(name in found for name in KERNELS[k])]
    log(f"{phase}: Mosaic kernels compiled: {sorted(found)}")
    if missing:
        raise SystemExit(f"{phase}: no tpu_custom_call for {missing}")


def check_matmul_kernels(phase: str, shapes, seed: int) -> None:
    """The Mosaic int4 and int8 matmuls against XLA's integer matmul on the
    chip. Integer-valued activations on the int4 grid with unit scales make
    every output an exact integer, so the three must be equal."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.packing import quantize_weight
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    for M, K, N in shapes:
        x = jnp.asarray(rng.integers(-7, 9, (M, K)), jnp.float32)
        w8 = jnp.asarray(rng.integers(-7, 9, (K, N)), jnp.int8)
        one = jnp.ones((1, N), jnp.float32)
        want = np.asarray(ref.int8_matmul_ref(x.astype(jnp.int8), w8, 1.0,
                                              one))
        wp, _ = quantize_weight(w8.astype(jnp.float32), one, 4)
        bad = {"int4": ops.int4_matmul(x, wp, jnp.float32(1), one, a_bits=4),
               "int8": ops.int8_matmul(x, w8, jnp.float32(1), one)}
        bad = {k: int(np.sum(np.asarray(v) != want)) for k, v in bad.items()}
        log(f"{phase}: M{M} K{K} N{N} kernel outputs != XLA integer matmul: "
            f"{bad}")
        if any(bad.values()):
            raise SystemExit(f"{phase}: Mosaic matmul is not exact")


def check_decode_attention(phase: str, cfg, seed: int, slots: int = 4,
                           max_len: int = 256) -> None:
    """Mosaic decode attention against XLA's ``cached_decode_attention`` on
    the same int8 and int4 caches, at the deployment's heads and slots.
    Both take their float dots at default precision, so they agree to
    rounding, not bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.kv_pack import dequantize_kv, quantize_kv
    from repro.models.attention import cached_decode_attention
    rng = np.random.default_rng(seed)
    B, S, H, dh = slots, max_len, cfg.num_kv_heads, cfg.hd
    q, k, v, kn, vn = (jnp.asarray(rng.standard_normal(s), jnp.float32)
                       for s in [(B, 1, H, dh), (B, S, H, dh), (B, S, H, dh),
                                 (B, 1, H, dh), (B, 1, H, dh)])
    lens = jnp.asarray(np.linspace(5, S - 1, B).round(), jnp.int32)
    xla = jax.jit(cached_decode_attention)
    for bits in (8, 4):
        kq, ks = quantize_kv(k, bits)
        vq, vs = quantize_kv(v, bits)
        got = ops.decode_attention(q[:, 0], kq, vq, ks, vs, kn[:, 0],
                                   vn[:, 0], lens)
        want = xla(q, dequantize_kv(kq, ks), dequantize_kv(vq, vs), kn, vn,
                   lens)[:, 0]
        d, rel = rel_diff(np.asarray(got), np.asarray(want))
        log(f"{phase}: decode attention kv{bits} B{B} S{S} H{H} dh{dh}: "
            f"Mosaic vs XLA max |d| {d:.6g} ({rel:.6g} of max, bound "
            f"{DECODE_ATTN_TOL})")
        if not rel <= DECODE_ATTN_TOL:
            raise SystemExit(f"{phase}: Mosaic decode attention disagrees "
                             f"with XLA")


def nudge_act_scales(model):
    """The same deployment with every activation scale one float32 ulp up:
    how far rounding alone moves this model's outputs."""
    import jax
    import jax.numpy as jnp

    from repro.deploy import DeployedModel

    def nudge(path, x):
        if getattr(path[-1], "key", None) == "s_a":
            return jnp.nextafter(x, jnp.inf)
        return x
    return DeployedModel(plan=model.plan, params=jax.tree_util
                         .tree_map_with_path(nudge, model.params))


def rel_diff(a, b) -> tuple[float, float]:
    """(max |a - b|, that over max |b|)."""
    import numpy as np
    d = float(np.max(np.abs(a - b)))
    return d, d / max(float(np.max(np.abs(b))), 1e-30)


# --------------------------------------------------------------- phase a
def phase_encoder(cfg, seed: int, n_requests: int = 16,
                  prefill_batch: int = 8):
    import jax
    import numpy as np

    from repro.core.policy import QuantPolicy
    from repro.deploy import ExecutionPlan, deploy, retarget_act_bits
    from repro.models.bert import init_bert_classifier
    from repro.serving import EncodeRequest, ServingEngine

    d, f = cfg.d_model, cfg.d_ff
    check_matmul_kernels("a", [(1024, d, f), (1024, f, d)], seed)
    L = cfg.num_layers
    plan = ExecutionPlan.build(
        cfg, QuantPolicy(num_layers=L, mode="int", last_k_int4=L),
        backend="pallas", mode="encoder", prefill_batch=prefill_batch,
        act_bits=4)
    params = init_bert_classifier(cfg, 2, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    calib = [{"tokens": rng.integers(1, cfg.vocab_size, (4, 16))
              .astype(np.int32)} for _ in range(4)]
    model = deploy(params, plan, calib)
    del params
    ref = retarget_act_bits(model, 4, backend="reference")
    lengths = np.linspace(16, 128, n_requests).round().astype(int)
    inputs = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
              for n in lengths]
    log(f"a: {plan.describe()}")
    log(f"a: {n_requests} classify requests, lengths {lengths.tolist()}")

    out = {}
    for name, m in (("pallas", model), ("reference", ref),
                    ("reference, scales +1 ulp", nudge_act_scales(ref))):
        eng = ServingEngine(m, slots=n_requests, max_len=int(lengths.max()))
        t0 = time.perf_counter()
        handles = [eng.submit_encode(EncodeRequest(tokens=t,
                                                   task="classify"))
                   for t in inputs]
        eng.run_until_drained()
        out[name] = np.stack([h.result().value for h in handles])
        log(f"a: {name}: served in {time.perf_counter() - t0:.3f}s "
            f"(compiles included)")
    p, r = out["pallas"], out["reference"]
    if p.shape != (n_requests, 2) or not np.all(np.isfinite(p)):
        raise SystemExit(f"a: bad logits {p.shape}, finite="
                         f"{bool(np.all(np.isfinite(p)))}")
    d, rel = rel_diff(p, r)
    same = int(np.sum(p.argmax(-1) == r.argmax(-1)))
    dn, reln = rel_diff(out["reference, scales +1 ulp"], r)
    log(f"a: pallas vs reference: max |dlogit| {d:.6g} ({rel:.6g} of max "
        f"|logit|, bound {ENCODER_LOGIT_TOL}); argmax equal {same}/"
        f"{n_requests}")
    log(f"a: reference vs itself with activation scales +1 ulp: max |dlogit| "
        f"{dn:.6g} ({reln:.6g} of max |logit|)")
    if same != n_requests or not rel <= ENCODER_LOGIT_TOL:
        raise SystemExit("a: pallas encoder disagrees with reference")


# --------------------------------------------------------------- phase b
def decode_deployment(cfg, seed: int, *, backend: str, tp: int = 1):
    """stablelm-style deployment: last half int4, the rest int8, int4 KV.

    The fp weights are drawn by one jitted init (no eager temporaries) and
    dropped once packed, so a model whose fp weights fill most of one chip
    deploys on it."""
    import jax

    from repro.core.policy import QuantPolicy
    from repro.deploy import ExecutionPlan, deploy
    from repro.models import api

    L = cfg.num_layers
    plan = ExecutionPlan.build(
        cfg, QuantPolicy(num_layers=L, mode="int", last_k_int4=L // 2),
        backend=backend, kv_bits=4, tp=tp)
    params = jax.jit(api.init_model, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return deploy(params, plan)


def decode_burst(seed: int, vocab: int, n: int = 8):
    import numpy as np
    rng = np.random.default_rng(seed)
    lengths = np.linspace(8, 64, n).round().astype(int)
    return [rng.integers(1, vocab, k).astype(np.int32) for k in lengths]


def serve_greedy(model, prompts, *, new_tokens: int = 16, slots: int = 4,
                 max_len: int = 256):
    from repro.serving import GenerationRequest, ServingEngine
    eng = ServingEngine(model, slots=slots, max_len=max_len)
    reqs = [GenerationRequest(prompt=p, max_new_tokens=new_tokens)
            for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return [list(map(int, r.out)) for r in reqs]


def first_step_logits(model, prompts):
    """Logits at each prompt's last position from one padded causal
    forward (the padding sits after every prompt, so it is never seen)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api
    width = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    fwd = jax.jit(lambda prm, t: api.forward(prm, model.plan, tokens=t)[0])
    logits = np.asarray(fwd(model.params, jnp.asarray(toks)))
    return np.stack([logits[i, len(p) - 1] for i, p in enumerate(prompts)])


def phase_decode(cfg, seed: int):
    import numpy as np

    from repro.deploy import retarget_act_bits
    d, f = cfg.d_model, cfg.d_ff
    check_matmul_kernels("b", [(4, d, f), (4, f, d), (200, f, d)], seed)
    check_decode_attention("b", cfg, seed)
    model = decode_deployment(cfg, seed, backend="pallas")
    ref = retarget_act_bits(model, model.plan.act_bits, backend="reference")
    prompts = decode_burst(seed, cfg.vocab_size)
    log(f"b: {model.plan.describe()}")
    log(f"b: 8 greedy requests, prompt lengths "
        f"{[len(p) for p in prompts]}, 16 new tokens each")
    streams, logits = {}, {}
    for name, m in (("pallas", model), ("reference", ref),
                    ("reference, scales +1 ulp", nudge_act_scales(ref))):
        t0 = time.perf_counter()
        streams[name] = serve_greedy(m, prompts)
        log(f"b: {name}: served in {time.perf_counter() - t0:.3f}s "
            f"(compiles included)")
        if name != "reference, scales +1 ulp":
            logits[name] = first_step_logits(m, prompts)
    p, r = logits["pallas"], logits["reference"]
    if not np.all(np.isfinite(p)) or p.shape != (len(prompts),
                                                 cfg.padded_vocab):
        raise SystemExit(f"b: bad first-step logits {p.shape}")
    d, rel = rel_diff(p[:, :cfg.vocab_size], r[:, :cfg.vocab_size])
    sp, sr = streams["pallas"], streams["reference"]
    agree = sum(_common_prefix(a, b) for a, b in zip(sp, sr))
    self_agree = sum(_common_prefix(a, b) for a, b in
                     zip(streams["reference, scales +1 ulp"], sr))
    total = sum(len(b) for b in sr)
    firsts = sum(a[0] == b[0] for a, b in zip(sp, sr))
    # a first token may differ only where the reference's own logits tie
    # the two tokens to within twice the measured logit difference
    ties = [i for i, (a, b) in enumerate(zip(sp, sr)) if a[0] != b[0]
            and r[i, b[0]] - r[i, a[0]] <= 2 * d]
    log(f"b: first-step logits max |dlogit| {d:.6g} ({rel:.6g} of max "
        f"|logit|, bound {DECODE_LOGIT_TOL}); first tokens equal "
        f"{firsts}/{len(prompts)} (near-ties {ties})")
    log(f"b: streams agree up to the first difference on {agree}/{total} "
        f"tokens; the reference agrees with itself with activation scales "
        f"+1 ulp on {self_agree}/{total}")
    if (not rel <= DECODE_LOGIT_TOL or firsts + len(ties) != len(prompts)
            or any(len(s) != 16 for s in sp)):
        raise SystemExit("b: pallas decode disagrees with reference")


def _common_prefix(a, b) -> int:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return n


# --------------------------------------------------------------- phase c
def phase_train(cfg, seed: int, ckpt_dir: Path, steps: int = 3,
                batch: int = 8, seq: int = 128):
    from repro.configs import TrainHParams
    from repro.core.policy import QuantPolicy
    from repro.data import lm_batches
    from repro.launch.train import run_training

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    L = cfg.num_layers
    policy = QuantPolicy(num_layers=L, mode="fake", last_k_int4=L,
                         grad_mode="mse")
    losses = []
    t0 = time.perf_counter()
    run_training(cfg, policy, TrainHParams(total_steps=steps),
                 iter(lm_batches(cfg.vocab_size, seq, batch, seed=seed)),
                 ckpt_dir=str(ckpt_dir), ckpt_every=0, log_every=0,
                 max_steps=steps,
                 on_step=lambda s, st, m: losses.append(
                     float(m["loss/train"])))
    log(f"c: {steps} QAT steps (LSQ-MSE, W4A4 fake quant), batch {batch}, "
        f"seq {seq}: losses {losses} in {time.perf_counter() - t0:.3f}s "
        f"(compile included)")
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise SystemExit("c: QAT losses missing or not finite")


# ------------------------------------------------------------ four chips
def four_chips(cfg, seed: int):
    import jax
    if len(jax.devices()) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{len(jax.devices())}")
    prompts = decode_burst(seed, cfg.vocab_size)
    streams = {}
    for tp in (4, 1):      # tp=4 first: its packing temporaries leave chip 0
        model = decode_deployment(cfg, seed, backend="reference", tp=tp)
        log(f"tp={tp}: {model.plan.describe()}")
        if tp == 4:
            held, total = {}, 0
            for leaf in jax.tree.leaves(model.params):
                total += leaf.nbytes
                for s in leaf.addressable_shards:
                    held[s.device.id] = (held.get(s.device.id, 0)
                                         + s.data.nbytes)
            log(f"tp=4: weight bytes held per device, of the model's "
                f"{total}: " + ", ".join(f"dev{d} {b} ({b / total:.4f})"
                                         for d, b in sorted(held.items())))
        t0 = time.perf_counter()
        streams[tp] = serve_greedy(model, prompts)
        log(f"tp={tp}: served in {time.perf_counter() - t0:.3f}s "
            f"(compiles included)")
        del model
    same = streams[4] == streams[1]
    agree = sum(_common_prefix(a, b) for a, b in zip(streams[4], streams[1]))
    log(f"tp=4 vs tp=1 streams byte-identical: {same} (agree up to the "
        f"first difference on {agree}/{sum(map(len, streams[1]))} tokens)")
    if not same:
        raise SystemExit("tp=4 streams differ from tp=1")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 vs tp=1 check (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / ".chip_smoke"),
                    help="work directory (IR dump, checkpoints)")
    args = ap.parse_args(argv)

    out = Path(args.out)
    dump = out / "ir_dump"
    shutil.rmtree(dump, ignore_errors=True)
    # excess precision off makes XLA round the float glue of both backends'
    # programs alike
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_allow_excess_precision=false").strip()
    import jax
    jax.config.update("jax_dump_ir_to", str(dump))

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r}")
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{enable_compile_cache()}")
    clock = CompileClock()
    seen: set = set()

    def timed(name, fn, *a):
        t0, c0 = time.perf_counter(), clock.total
        fn(*a)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"{name}: {time.perf_counter() - t0:.3f}s wall, "
            f"{clock.total - c0:.3f}s backend compile, peak device memory "
            f"so far {peak} bytes")

    stablelm = get_config("stablelm-3b")
    if args.four_chips:
        timed("four-chips", four_chips, stablelm, args.seed)
    else:
        bert = get_config("bert-base")
        timed("a encoder", phase_encoder, bert, args.seed)
        require_kernels("a", kernels_in(dump, seen), ("int4", "act_quant"))
        timed("b decode", phase_decode, stablelm, args.seed)
        require_kernels("b", kernels_in(dump, seen),
                        ("int4", "int8", "act_quant", "decode_attention"))
        timed("c train", phase_train, bert, args.seed, out / "train_ckpt")
    shutil.rmtree(out, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
