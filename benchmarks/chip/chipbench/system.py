"""Build the system under test for a configuration file: the program's
model config, plan, deployment and serving engine."""
from __future__ import annotations

import numpy as np

#: configuration-file key -> the program's ModelConfig field
SIZE_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "num_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size"}


def model_config(config: dict):
    """The program's registered config, held to the sizes the file states;
    keys under ``program_overrides`` replace registry fields the file says
    it runs differently (listed in its ``reduced``)."""
    from repro.configs import get_config
    cfg = get_config(config["registry"])
    over = config.get("program_overrides", {})
    if over:
        cfg = cfg.replace(**over)
    for key, field in SIZE_KEYS.items():
        if key in config and getattr(cfg, field) != config[key]:
            raise ValueError(
                f"{config['name']}: the file states {key}={config[key]} but "
                f"the program's {config['registry']!r} has {field}="
                f"{getattr(cfg, field)}")
    return cfg


def build_engine(config: dict, seed: int, reference) -> tuple:
    """(engine, plan): weights from the seed, deployed with activation
    scales calibrated on the seeded batches, behind a ServingEngine."""
    import jax

    from repro.core.policy import QuantPolicy
    from repro.deploy import ExecutionPlan, deploy
    from repro.serving import ServingEngine

    cfg = model_config(config)
    p, e = config["plan"], config["engine"]
    policy = QuantPolicy(num_layers=cfg.num_layers, mode="int",
                         last_k_int4=p["last_k_int4"])
    plan = ExecutionPlan.build(
        cfg, policy, backend=p["backend"], mode=p["mode"],
        kv_bits=p.get("kv_bits"), act_bits=p.get("act_bits"),
        prefill_batch=e["prefill_batch"])
    params = reference.init_params(config, seed)
    calib = [{"tokens": t} for t in reference.calib_tokens(config, seed)]
    model = deploy(params, plan, calib)
    del params
    jax.block_until_ready(jax.tree.leaves(model.params))
    engine = ServingEngine(model, slots=e["slots"], max_len=e["max_len"])
    return engine, plan


def buckets(lo: int, hi: int, max_len: int, smallest: int = 8) -> list:
    """The prefill buckets that prompt lengths ``lo..hi`` fall into under
    the engine's doubling ladder (8, 16, ... capped at max_len)."""
    out, b = [], smallest
    while True:
        top = min(b, max_len)
        if top >= lo and (b // 2 if b > smallest else 0) < hi:
            out.append(top)
        if b >= max_len or b >= hi:
            break
        b *= 2
    return sorted(set(out))


def warm_shapes(pump, traffic: dict, config: dict, vocab: int) -> int:
    """Run every (bucket, group size) the traffic can produce through the
    public API. Returns the number of warm-up requests."""
    from .traffic import Arrival
    e = config["engine"]
    pl = traffic["prompt_len"]
    ns, n = [], 1
    while n <= e["prefill_batch"]:
        ns.append(n)
        n *= 2
    count = 0
    rng = np.random.default_rng(0)
    for b in buckets(int(pl["min"]), int(pl["max"]), e["max_len"]):
        length = min(b, int(pl["max"]))
        for n in ns:
            for _ in range(n):
                toks = rng.integers(1, vocab, length).astype(np.int32)
                pump.submit(Arrival(-1, 0.0, toks), 0.0)
                count += 1
            pump.drain(float("inf"))
    pump.reqs.clear()
    pump.steps.clear()
    return count
