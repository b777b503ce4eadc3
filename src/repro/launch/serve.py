"""Thin CLI shim over the serving subsystem (repro/serving — DESIGN.md
§7/§9/§10).

Entry modes:

* default            build an ExecutionPlan, deploy an int model in-process,
                     serve a synthetic burst (smoke/demo path);
* ``--export DIR``   additionally save the DeployedModel artifact to DIR;
* ``--artifact DIR`` load a previously exported artifact and serve it —
                     no fp weights are initialized and nothing recalibrates;
                     token streams are byte-identical to the in-memory run
                     that exported it;
* ``--mode encoder`` prefill-only serving (DESIGN.md §14): deploys an int4
                     BERT classifier (or loads one with --artifact) and
                     serves a burst of ``EncodeRequest``\\ s (``--task``
                     classify/embed/score) — no decode loop, no KV;
* ``--tenant NAME=DIR`` (repeatable) multi-tenant serving: each NAME loads
                     the artifact at DIR into one ``MultiTenantEngine``
                     (shared clock/metrics, deficit-round-robin fair share);
                     the burst round-robins across tenants, encode traffic
                     for encoder artifacts and generation otherwise.

Generation flags map onto the §10 API: ``--temperature/--top-k/--top-p/
--seed`` build the burst's ``SamplingParams`` (temperature 0 = greedy),
``--n`` fans each prompt into n independently-seeded sample streams,
``--stop`` sets stop-token ids, and ``--stream`` prints each token as the
engine emits it (the TokenStream callback form). ``--kv-paging paged``
(+ optional ``--kv-budget-mb``) serves the burst out of the §15 paged
block pool; ``--policy-from search.json`` deploys the exact per-layer bit
assignment a §13 auto-search run chose.

Scale axes (DESIGN.md §16): ``--tp N`` shards the deployed weights and KV
heads over N devices (with ``--artifact`` it RESHARDS the saved layout to
N at load); ``--replicas N`` serves the burst through a data-parallel
``ReplicaSet`` of N engines over the one deployed model; ``--warmup``
pre-compiles every (bucket, batch) prefill/decode shape before traffic so
the first request pays no jit cost (the first-vs-steady split shows up in
the metrics report).

The engine itself lives in ``repro.serving``; plans/artifacts in
``repro.deploy``. ``Request`` and ``ServingEngine`` stay importable from
here for backward compatibility.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..serving import (EncodeRequest, GenerationRequest,  # noqa: F401
                       MultiTenantEngine, QueueFullError,
                       Request, SamplingParams, ServingEngine)  # (compat)


def _build_encoder_model(args):
    """In-process int4 W4A4 BERT classifier artifact for --mode encoder:
    the paper's deployment target (``--arch`` tinybert4 or bert-base),
    calibrated on a small synthetic batch."""
    import jax

    from ..configs import get_config, reduced
    from ..core.policy import QuantPolicy
    from ..deploy import ExecutionPlan, deploy
    from ..models.bert import init_bert_classifier

    cfg = get_config(args.arch or "tinybert4")
    if args.reduced:
        cfg = reduced(cfg)
    n_units = cfg.num_layers
    k4 = args.int4_last_k if args.int4_last_k >= 0 else n_units
    policy = QuantPolicy(num_layers=n_units, mode="int", last_k_int4=k4)
    plan = ExecutionPlan.build(cfg, policy, backend=args.backend,
                               mode="encoder",
                               prefill_batch=max(args.prefill_batch, 1),
                               act_bits=args.act_bits,
                               tp=args.tp or 1)
    params = init_bert_classifier(cfg, 2, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    calib = [{"tokens": rng.integers(1, cfg.vocab_size,
                                     (4, 16)).astype(np.int32)}
             for _ in range(4)]
    return deploy(params, plan, calib)


def _build_model(args):
    """ExecutionPlan + in-process deployment (the non-artifact path)."""
    import jax

    from ..configs import get_config, reduced
    from ..core.policy import QuantPolicy
    from ..deploy import ExecutionPlan, deploy
    from ..models import api

    cfg = get_config(args.arch or "stablelm-3b")
    if args.reduced:
        cfg = reduced(cfg)
    n_units = cfg.dec_layers if cfg.family == "encdec" else cfg.num_layers
    if args.policy_from:
        from ..core.autosearch import load_search_policy
        policy = load_search_policy(args.policy_from, n_units)
        print(f"[serve] policy from {args.policy_from}: {policy.describe()}")
    else:
        k4 = args.int4_last_k if args.int4_last_k >= 0 else n_units // 2
        policy = QuantPolicy(num_layers=n_units, mode="int", last_k_int4=k4)
    plan = ExecutionPlan.build(cfg, policy, backend=args.backend,
                               kv_bits=args.kv_bits,
                               prefill_mode=args.prefill_mode,
                               prefix_cache=int(args.prefix_cache_mb
                                                * (1 << 20)),
                               prefill_batch=args.prefill_batch,
                               act_bits=args.act_bits,
                               kv_paging=args.kv_paging,
                               tp=args.tp or 1)
    params = api.init_model(cfg, jax.random.PRNGKey(0))
    return deploy(params, plan)


def main(argv=None):
    from ..deploy import DeployedModel

    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None,
                   help="model from the config registry; default "
                        "stablelm-3b, or tinybert4 with --mode encoder "
                        "(which takes tinybert4 or bert-base)")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--mode", default="decode",
                   choices=["decode", "encoder"],
                   help="'encoder' serves prefill-only EncodeRequests "
                        "(DESIGN.md §14) over an int4 BERT classifier "
                        "artifact — one batched bidirectional forward per "
                        "request, no decode loop")
    p.add_argument("--task", default="classify",
                   choices=["classify", "embed", "score"],
                   help="what the --mode encoder burst asks for per request")
    p.add_argument("--tenant", action="append", default=None,
                   metavar="NAME=DIR",
                   help="repeatable: host the artifact at DIR as tenant "
                        "NAME in one MultiTenantEngine (deficit-round-robin "
                        "fair share; encoder and decoder artifacts mix)")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--max-queue", type=int, default=None,
                   help="bound the pending queue (submit raises "
                        "QueueFullError past it; default unbounded)")
    p.add_argument("--int4-last-k", type=int, default=-1)
    p.add_argument("--prefill-mode", default="auto",
                   choices=["auto", "chunked", "token"])
    p.add_argument("--backend", default="reference",
                   choices=["reference", "pallas"],
                   help="'pallas' routes matmuls through the int4/int8 "
                        "Pallas kernels (fused decode epilogue): Mosaic on "
                        "TPU, interpret mode on CPU, refused elsewhere")
    p.add_argument("--kv-bits", type=int, default=16, choices=[16, 8, 4],
                   help="serving KV-cache precision (DESIGN.md §8): 16 keeps "
                        "fp rows; 8/4 store packed codes + per-(token, head) "
                        "scales and decode via the fused Pallas "
                        "decode-attention kernel with --backend pallas")
    p.add_argument("--prefix-cache-mb", type=float, default=0.0,
                   help="shared-prefix KV reuse budget in MiB (DESIGN.md "
                        "§11): cached quantized prefix rows scatter into "
                        "new slots and only the prompt suffix prefills; "
                        "0 disables")
    p.add_argument("--kv-paging", default="dense",
                   choices=["dense", "paged"],
                   help="KV-cache memory layout (DESIGN.md §15): 'paged' "
                        "serves slots, shared prefixes and copy-on-write "
                        "forks out of one refcounted block pool under one "
                        "byte budget (admission + LRU eviction), with "
                        "token streams bit-identical to 'dense'")
    p.add_argument("--kv-budget-mb", type=float, default=None,
                   help="paged KV pool byte budget in MiB (requires "
                        "--kv-paging paged); default sizes the pool to "
                        "exactly the dense slots*max_len capacity, so "
                        "flipping --kv-paging alone never changes capacity")
    p.add_argument("--policy-from", default=None, metavar="JSON",
                   help="load the mixed-precision QuantPolicy from a "
                        "search artifact (benchmarks/table1_glue.py "
                        "--search output, or a bare policy dump) instead "
                        "of the --int4-last-k heuristic — serve exactly "
                        "the per-layer bit assignment the auto-search "
                        "chose (DESIGN.md §13)")
    p.add_argument("--n", type=int, default=1,
                   help="samples per burst prompt: n > 1 fans each request "
                        "into n independent streams (seeded per sample "
                        "index); a paged engine shares the prompt's KV "
                        "blocks copy-on-write across the samples")
    p.add_argument("--act-bits", type=int, default=None,
                   choices=[0, 4, 8],
                   help="activation precision override (DESIGN.md §13): "
                        "4/8 quantize every quantized segment's activations "
                        "onto that grid (W4A4 serving; calibrated scales are "
                        "retargeted by the qmax ratio), 0 keeps activations "
                        "fp against dequantized weights (reference backend; "
                        "the parity baseline); default follows the policy. "
                        "With --artifact, retargets the loaded model")
    p.add_argument("--prefill-batch", type=int, default=1,
                   help="group up to N same-bucket admissions into one "
                        "batch-N prefill forward (compiled per (bucket, n), "
                        "n padded to a power of two); 1 keeps serial "
                        "prefills")
    p.add_argument("--shared-prefix", type=int, default=0, metavar="T",
                   help="give every synthetic burst request the same "
                        "T-token prompt prefix (demo workload for "
                        "--prefix-cache-mb)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature (0 = greedy argmax, the "
                        "legacy path)")
    p.add_argument("--top-k", type=int, default=0,
                   help="keep only the k highest logits (0 disables)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling mass (1.0 disables)")
    p.add_argument("--seed", type=int, default=0,
                   help="sampling seed; streams are deterministic per "
                        "(prompt, seed) regardless of batching")
    p.add_argument("--stop", default=None, metavar="ID[,ID...]",
                   help="comma-separated stop-token ids: emitting one ends "
                        "the request early (finish_reason='stop')")
    p.add_argument("--stream", action="store_true",
                   help="print every token as the engine emits it "
                        "(TokenStream callback form)")
    p.add_argument("--tp", type=int, default=None, metavar="N",
                   help="tensor-parallel degree (DESIGN.md §16): shard "
                        "packed weights + KV heads over N devices on a "
                        "('model',) mesh; with --artifact, RESHARDS the "
                        "saved layout to N at load (a tp=2 export serves "
                        "at tp=1 or tp=4); default keeps the recorded "
                        "layout (or 1 when building in-process)")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="data-parallel replica count (DESIGN.md §16): N "
                        "engines over the ONE deployed model behind one "
                        "admission queue (least-loaded dispatch, shared "
                        "rid space); composes with --tp")
    p.add_argument("--warmup", action="store_true",
                   help="pre-compile every (bucket, batch) prefill/decode "
                        "shape before serving traffic, so no request pays "
                        "first-call jit cost (the first-vs-steady latency "
                        "split stays visible in the metrics report)")
    p.add_argument("--artifact", default=None, metavar="DIR",
                   help="serve a saved DeployedModel (repro.deploy) — no fp "
                        "weights, no recalibration; plan/arch flags come "
                        "from the artifact")
    p.add_argument("--export", default=None, metavar="DIR",
                   help="save the deployed model as an artifact before "
                        "serving (reload later with --artifact DIR)")
    args = p.parse_args(argv)
    if args.artifact and args.export:
        p.error("--export builds a fresh model and cannot be combined with "
                "--artifact (which serves an existing one)")
    if args.artifact and args.kv_paging == "paged":
        p.error("--artifact serves the artifact's own plan (including its "
                "kv_paging axis); export the model with --kv-paging paged "
                "instead of overriding it at load time")
    if args.kv_budget_mb is not None and not args.artifact \
            and args.kv_paging != "paged":
        p.error("--kv-budget-mb sizes the paged KV pool; it needs "
                "--kv-paging paged (or a paged artifact)")
    if args.n < 1:
        p.error(f"--n must be >= 1, got {args.n}")
    if args.tp is not None and args.tp < 1:
        p.error(f"--tp must be >= 1, got {args.tp}")
    if args.replicas < 1:
        p.error(f"--replicas must be >= 1, got {args.replicas}")
    if args.tenant and (args.tp is not None or args.replicas > 1):
        p.error("--tenant engines serve each artifact's own recorded "
                "layout; --tp/--replicas apply to single-model serving")
    if args.tenant:
        if args.artifact or args.export:
            p.error("--tenant hosts saved artifacts; it cannot be combined "
                    "with --artifact/--export")
        return _main_tenants(args)

    if args.artifact:
        model = DeployedModel.load(args.artifact, tp=args.tp)
        if (args.act_bits is not None
                and args.act_bits != model.plan.act_bits):
            from ..deploy import retarget_act_bits
            model = retarget_act_bits(model, args.act_bits)
            print(f"[serve] retargeted activations to "
                  f"{'fp' if args.act_bits == 0 else f'{args.act_bits}-bit'}")
        print(f"[serve] loaded artifact {args.artifact}: "
              f"{model.plan.describe()}")
    else:
        model = (_build_encoder_model(args) if args.mode == "encoder"
                 else _build_model(args))
        if args.export:
            path = model.save(args.export)
            print(f"[serve] exported artifact to {path}")
    if args.mode == "encoder" and model.plan.mode != "encoder":
        p.error(f"--mode encoder needs a mode='encoder' artifact; "
                f"{args.artifact or 'the built model'} is "
                f"mode={model.plan.mode!r}")

    cfg = model.plan.cfg
    kv_budget = (int(args.kv_budget_mb * (1 << 20))
                 if args.kv_budget_mb is not None else None)
    if args.replicas > 1:
        from ..serving import ReplicaSet
        eng = ReplicaSet(model, replicas=args.replicas, slots=args.slots,
                         max_len=args.max_len, max_queue=args.max_queue,
                         kv_budget_bytes=kv_budget, warmup=args.warmup)
        print(f"[serve] replica set: {args.replicas} engines, "
              f"{args.slots} slots each")
    else:
        eng = ServingEngine(model, slots=args.slots, max_len=args.max_len,
                            max_queue=args.max_queue,
                            kv_budget_bytes=kv_budget, warmup=args.warmup)
    if model.plan.mode == "encoder":
        return _serve_encoder_burst(args, eng, cfg)
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed, n=args.n)
    stop = (frozenset(int(t) for t in args.stop.split(","))
            if args.stop else frozenset())
    on_token = ((lambda rid, tok: print(f"[stream] rid={rid} tok={tok}"))
                if args.stream else None)

    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size,
                          args.shared_prefix).astype(np.int32)
    t0 = time.time()
    steps = 0
    for _ in range(args.requests):
        plen = int(rng.integers(4, 12))
        tail = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        req = GenerationRequest(
            prompt=np.concatenate([shared, tail]),
            max_new_tokens=8, sampling=sampling, stop_tokens=stop)
        while True:
            try:
                eng.submit(req, on_token=on_token)
                break
            except QueueFullError:       # backpressure: drain a round, retry
                eng.engine_step()
                steps += 1
    steps += eng.run_until_drained()
    dt = time.time() - t0
    finished = eng.pop_done()
    total_tokens = sum(len(r.out) for r in finished)
    stopped = sum(r.finish_reason == "stop" for r in finished)
    print(f"[serve] {len(finished)} requests, {total_tokens} tokens, "
          f"{steps} engine steps, {dt:.2f}s "
          f"({total_tokens / max(dt, 1e-9):.1f} tok/s, "
          f"{stopped} stop-token exits)")
    print(f"[serve] {eng.metrics.report()}")


def _serve_encoder_burst(args, eng, cfg):
    """Synthetic prefill-only burst (DESIGN.md §14): submit EncodeRequests,
    drain, report — the encoder-mode analogue of the generation burst."""
    rng = np.random.default_rng(0)
    t0 = time.time()
    steps = 0
    handles = []
    for _ in range(args.requests):
        plen = int(rng.integers(4, 17))
        toks = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        req = EncodeRequest(tokens=toks, task=args.task)
        while True:
            try:
                handles.append(eng.submit_encode(req))
                break
            except QueueFullError:       # backpressure: drain a round, retry
                eng.engine_step()
                steps += 1
    steps += eng.run_until_drained()
    dt = time.time() - t0
    finished = eng.pop_done()
    done = sum(r.finish_reason == "done" for r in finished)
    total = sum(len(r.tokens) for r in finished)
    print(f"[serve] encoder burst: {len(finished)} requests ({done} done), "
          f"{total} input tokens, {steps} engine steps, {dt:.2f}s "
          f"({total / max(dt, 1e-9):.1f} tok/s, task={args.task})")
    print(f"[serve] {eng.metrics.report()}")


def _main_tenants(args):
    """--tenant NAME=DIR...: host every artifact in one MultiTenantEngine
    and round-robin a synthetic burst across tenants (encode traffic for
    encoder artifacts, generation otherwise)."""
    from ..deploy import DeployedModel

    pairs = []
    for spec in args.tenant:
        name, sep, path = spec.partition("=")
        if not sep or not name or not path:
            raise SystemExit(f"--tenant expects NAME=DIR, got {spec!r}")
        pairs.append((name, path))

    mt = MultiTenantEngine()
    for name, path in pairs:
        model = DeployedModel.load(path)
        mt.add_tenant(name, model, slots=args.slots, max_len=args.max_len,
                      max_queue=args.max_queue)
        print(f"[serve] tenant {name!r}: {model.plan.describe()}")

    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed)
    rng = np.random.default_rng(0)
    t0 = time.time()
    steps = 0
    for i in range(args.requests):
        name = pairs[i % len(pairs)][0]
        t = mt.tenants[name]
        vocab = t.engine.cfg.vocab_size
        plen = int(rng.integers(4, 12))
        toks = rng.integers(1, vocab, plen).astype(np.int32)
        while True:
            try:
                if t.engine.mode == "encoder":
                    mt.submit_encode(EncodeRequest(tokens=toks,
                                                   task=args.task),
                                     tenant=name)
                else:
                    mt.submit(GenerationRequest(prompt=toks,
                                                max_new_tokens=8,
                                                sampling=sampling),
                              tenant=name)
                break
            except QueueFullError:       # backpressure: drain a round, retry
                mt.engine_step()
                steps += 1
    steps += mt.run_until_drained()
    dt = time.time() - t0
    finished = mt.pop_done()
    print(f"[serve] multi-tenant burst: {len(finished)} requests over "
          f"{len(pairs)} tenants, {steps} engine steps, {dt:.2f}s")
    print(f"[serve] {mt.metrics.report()}")


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
