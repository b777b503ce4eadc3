"""Serve a quantized model with batched, streaming requests (the paper's
deployment).

The deployment flow (DESIGN.md §9): build an ``ExecutionPlan`` (segments +
kernel selection + KV precision resolved once), ``deploy()`` the packed
int4/int8 ``DeployedModel``, ``save()`` it, then serve the RELOADED artifact
through the continuous-batching engine (``repro.serving``, DESIGN.md §7) —
chunked prefill, slot-isolated KV cache, latency metrics. The serve side
never touches fp weights and never recalibrates, and its token streams are
byte-identical to serving the in-memory model (asserted below).

The generation API (DESIGN.md §10) on display here:

* greedy ``GenerationRequest`` bursts drained via ``run_until_drained`` and
  ``pop_done()`` (no unbounded done-list growth);
* a sampled request (temperature/top-k/seed) iterated token-by-token through
  its ``TokenStream`` — same tokens every run, per-request determinism;
* a stop-token request that releases its slot early.

Pass backend="pallas" to route matmuls through the int4/int8 Pallas kernels
(fused dequant+bias+GELU decode epilogue; Mosaic on TPU, interpret mode on
CPU).

Run:  PYTHONPATH=src python examples/serve_int4.py [--quick]
"""
import argparse
import tempfile
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core.policy import QuantPolicy
from repro.deploy import DeployedModel, ExecutionPlan, deploy
from repro.models import api
from repro.serving import GenerationRequest, SamplingParams, ServingEngine


def _burst(eng, cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        plen = int(rng.integers(4, 16))
        eng.submit(GenerationRequest(
            prompt=rng.integers(1, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=8))
    steps = eng.run_until_drained()
    return steps, {r.rid: r.out.tolist() for r in eng.pop_done()}


def main(quick: bool = False):
    cfg = reduced(get_config("qwen2.5-32b"))
    n = cfg.num_layers
    n_requests = 4 if quick else 12
    policy = QuantPolicy(num_layers=n, mode="int", last_k_int4=n // 2)
    # kv_bits=8 stores the KV cache as int8 codes + per-(token, head)
    # scales (DESIGN.md §8) — 4 packs int4 nibbles, 16 keeps fp rows
    plan = ExecutionPlan.build(cfg, policy, kv_bits=8)

    params = api.init_model(cfg, jax.random.PRNGKey(0))
    model = deploy(params, plan)
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(model.params))
    n_fp = sum(x.size * 4 for x in jax.tree.leaves(params))
    print(f"deployed weights: {n_bytes/1e6:.2f}MB vs fp32 {n_fp/1e6:.2f}MB "
          f"({n_fp/n_bytes:.1f}x reduction)")

    # serve the in-memory model, then the saved+reloaded artifact: identical
    eng = ServingEngine(model, slots=4, max_len=128)
    t0 = time.time()
    steps, mem_streams = _burst(eng, cfg, n_requests)
    dt = time.time() - t0
    toks = sum(len(v) for v in mem_streams.values())
    print(f"served {len(mem_streams)} requests / {toks} tokens in {steps} "
          f"engine steps, {dt:.2f}s ({toks/dt:.1f} tok/s on CPU)")
    print("metrics:", eng.metrics.report())

    with tempfile.TemporaryDirectory() as td:
        loaded = DeployedModel.load(model.save(f"{td}/artifact"))
    eng2 = loaded.engine(slots=4, max_len=128)
    _, art_streams = _burst(eng2, cfg, n_requests)
    assert art_streams == mem_streams, "artifact streams diverged!"
    print(f"artifact round trip: {len(art_streams)} requests byte-identical")
    print("sample output:", art_streams[0])

    # --- streaming + sampling (DESIGN.md §10): iterate tokens as produced
    stream = eng2.submit(GenerationRequest(
        prompt=np.array([5, 9, 2, 7], np.int32), max_new_tokens=8,
        sampling=SamplingParams(temperature=0.8, top_k=40, seed=42)))
    sampled = [tok for tok in stream]      # pumps the engine under the hood
    print(f"sampled stream (T=0.8, top_k=40, seed=42): {sampled} "
          f"[{stream.finish_reason}]")

    # --- stop tokens: the request ends the moment it emits one, freeing
    # its slot for queued work instead of decoding to max_new_tokens
    stop = eng2.submit(GenerationRequest(
        prompt=np.array([5, 9, 2, 7], np.int32), max_new_tokens=64,
        stop_tokens={sampled[2]},      # same seed → same stream → stops early
        sampling=SamplingParams(temperature=0.8, top_k=40, seed=42)))
    r = stop.result()
    assert r.finish_reason == "stop" and len(r.tokens) <= 3, r
    print(f"stop-token request: {len(r.tokens)}/64 tokens "
          f"[{r.finish_reason}] — slot released early")
    eng2.pop_done()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: smaller burst")
    main(quick=ap.parse_args().quick)
