"""Plain reference for the BERT family under int4/int8 weights and
activations.

It imports nothing of the program. It makes the float32 weights from the
seed (the same function hands them to the program's ``deploy()``),
calibrates its own activation scales on the same seeded token batches,
quantizes the weights itself, and runs the encoder in straightforward
``jax.numpy``:

* weights: per output channel, s_w = max|w| / 8 for 4 bits (127 for 8),
  codes round(clip(w / s_w, -7, 8)) (the paper's k=4 grid);
* activations: per tensor, s_a = (99.99th percentile of |input| in the
  unquantized float model, the largest over the calibration batches) / 8,
  codes clip(round(x / s_a), -7, 8); integer products summed in int32 and
  scaled by s_a * s_w;
* post-LayerNorm blocks, bidirectional attention with keys past a row's
  length masked, GELU (tanh form), float32 softmax and LayerNorm, a final
  LayerNorm, then the tanh pooler on position 0 and the classifier.

Float dots run at XLA's default precision, the precision the program's
configuration states (it sets none). ``dtype="bfloat16"`` is the control:
every float tensor between the integer matmuls held in bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SITES = ("qkv", "wo", "w1", "w2")       # activation-scale sites per layer
POS_ROWS = 8192                         # the program's position table
LN_EPS = 1e-5
COMPARED = ("median_dev", "mean_dev")


def key_of(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def dims(config: dict) -> dict:
    vocab = config["vocab_size"]
    return dict(L=config["num_hidden_layers"], d=config["hidden_size"],
                H=config["num_attention_heads"], f=config["intermediate_size"],
                Vp=(vocab + 255) // 256 * 256, labels=config["num_labels"])


def weight_bits(config: dict) -> list:
    L, k4 = config["num_hidden_layers"], config["plan"]["last_k_int4"]
    return [4 if l >= L - k4 else 8 for l in range(L)]


def _qrange(bits: int):
    return (-127, 127) if bits >= 8 else (-(2 ** (bits - 1)) + 1,
                                          2 ** (bits - 1))


# ------------------------------------------------------------- weights
def _normal(key, tag: int, shape, std: float):
    return jax.random.normal(jax.random.fold_in(key, tag), shape,
                             jnp.float32) * std


def _layer(key, l: int, D: dict, std: float) -> dict:
    k = jax.random.fold_in(jax.random.fold_in(key, 100), l)
    d, f = D["d"], D["f"]

    def lin(tag, n_in, n_out):
        return {"w": _normal(k, tag, (n_in, n_out), std),
                "b": _normal(k, tag + 1, (n_out,), std)}
    return {"ln1": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "attn": {"wq": lin(0, d, d), "wk": lin(2, d, d),
                     "wv": lin(4, d, d), "wo": lin(6, d, d)},
            "ln2": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "ffn": {"w1": lin(8, d, f), "w2": lin(10, f, d)}}


def init_params(config: dict, seed: int) -> dict:
    """The float32 weights in the program's layout (layers stacked, unit
    quantization scales that ``deploy()`` recalibrates), made on the device
    in one jitted call."""
    D, std = dims(config), float(config["init_std"])
    d = D["d"]

    @jax.jit
    def make(key):
        layers = [_layer(key, l, D, std) for l in range(D["L"])]
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *layers)
        for lin in (*stacked["attn"].values(), *stacked["ffn"].values()):
            lin["s_w"] = jnp.ones((D["L"], 1, lin["w"].shape[-1]))
            lin["s_a"] = jnp.ones((D["L"],))
        return {"embed": _normal(key, 1, (D["Vp"], d), std),
                "pos_embed": _normal(key, 2, (POS_ROWS, d), std),
                "final_norm": {"scale": jnp.ones((d,)),
                               "bias": jnp.zeros((d,))},
                "pooler": {"w": _normal(key, 3, (d, d), std),
                           "b": _normal(key, 4, (d,), std)},
                "classifier": {"w": _normal(key, 5, (d, D["labels"]), std),
                               "b": _normal(key, 6, (D["labels"],), std)},
                "layers": stacked}
    return make(key_of(seed))


def calib_tokens(config: dict, seed: int) -> list:
    c = config["calibration"]
    rng = np.random.default_rng([int(seed), 7])
    return [rng.integers(1, config["vocab_size"], (c["batch"], c["seq"]))
            .astype(np.int32) for _ in range(c["batches"])]


# --------------------------------------------------------- calibration
def _layernorm(x, p):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + LN_EPS)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _site_inputs(lp, x, H: int):
    """One layer of the unquantized float32 model, op by op: the inputs of
    its four quantization sites, and its output."""
    B, S, d = x.shape
    lin = lambda h, p: h @ p["w"] + p["b"]
    q = lin(x, lp["attn"]["wq"]).reshape(B, S, H, -1)
    k = lin(x, lp["attn"]["wk"]).reshape(B, S, H, -1)
    v = lin(x, lp["attn"]["wv"]).reshape(B, S, H, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(d // H))
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    o = o.reshape(B, S, d)
    x1 = _layernorm(x + lin(o, lp["attn"]["wo"]), lp["ln1"])
    g = jax.nn.gelu(lin(x1, lp["ffn"]["w1"]), approximate=True)
    out = _layernorm(x1 + lin(g, lp["ffn"]["w2"]), lp["ln2"])
    return (x, o, x1, g), out


def calibrate(config: dict, seed: int, G: dict, layers: list) -> np.ndarray:
    """s_a[layer, site]: the largest batch's percentile over the bits' qmax.

    The float model runs op by op, as a calibration pass over a handful of
    batches naturally does. A W4A4 network turns a scale one float32 ulp
    away into a different answer for many requests, so the scales must
    come from the float model's own rounding, not from one fused
    differently."""
    D = dims(config)
    pct = float(config["calibration"]["percentile"])
    stats = np.zeros((D["L"], len(SITES)), np.float32)
    for toks in calib_tokens(config, seed):
        S = toks.shape[1]
        x = G["embed"][jnp.asarray(toks)] + G["pos_embed"][0:S][None]
        for l, lp in enumerate(layers):
            ins, x = _site_inputs(lp, x, D["H"])
            for j, a in enumerate(ins):
                a = np.abs(np.asarray(a, np.float32)).reshape(-1)
                stats[l, j] = max(stats[l, j],
                                  np.float32(np.percentile(a, pct)))
    qmax = np.array([_qrange(b)[1] for b in weight_bits(config)],
                    np.float32)[:, None]
    return np.maximum(stats / qmax, np.float32(1e-8))


def _quantize_weight(w, bits: int):
    lo, hi = _qrange(bits)
    s_w = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / hi, 1e-8)
    return jnp.clip(jnp.round(w / s_w), lo, hi).astype(jnp.int8), s_w


def quantized_segments(config: dict, layers: list, s_a) -> list:
    """[(bits, layers stacked)] for each run of layers of equal bits."""
    bits = weight_bits(config)
    qs = []
    for l, lp in enumerate(layers):
        ql = {"ln1": lp["ln1"], "ln2": lp["ln2"], "attn": {}, "ffn": {}}
        for grp, name, site in (("attn", "wq", 0), ("attn", "wk", 0),
                                ("attn", "wv", 0), ("attn", "wo", 1),
                                ("ffn", "w1", 2), ("ffn", "w2", 3)):
            codes, s_w = _quantize_weight(lp[grp][name]["w"], bits[l])
            ql[grp][name] = {"codes": codes, "s_w": s_w,
                             "s_a": jnp.float32(s_a[l, site]),
                             "b": lp[grp][name]["b"]}
        qs.append(ql)
    segs, start = [], 0
    for l in range(1, len(qs) + 1):
        if l == len(qs) or bits[l] != bits[start]:
            segs.append((bits[start], jax.tree.map(
                lambda *a: jnp.stack(a), *qs[start:l])))
            start = l
    return segs


def prepare(config: dict, seed: int) -> tuple:
    """(globals, quantized segments): weights from the seed, activation
    scales calibrated on the seeded batches."""
    params = init_params(config, seed)
    L = config["num_hidden_layers"]
    layers = [jax.tree.map(lambda a, l=l: a[l], params["layers"])
              for l in range(L)]
    G = {k: v for k, v in params.items() if k != "layers"}
    s_a = calibrate(config, seed, G, layers)
    return G, quantized_segments(config, layers, s_a)


# ------------------------------------------------------ quantized model
def _ln(x, p, ft):
    return _layernorm(x, p).astype(ft)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _qlin(x, q, ft, bits):
    """x (..., K) float -> codes -> int32 product -> float (..., N)."""
    lo, hi = _qrange(bits)
    xc = jnp.clip(jnp.round(x.astype(ft) / q["s_a"].astype(ft)), lo, hi)
    acc = jax.lax.dot_general(xc.astype(jnp.int8), q["codes"],
                              (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    scale = (q["s_a"] * q["s_w"]).astype(ft)
    return (acc.astype(ft) * scale + q["b"].astype(ft)).astype(ft)


def _flin(x, p, ft):
    return (x.astype(ft) @ p["w"].astype(ft) + p["b"].astype(ft)).astype(ft)


def _attention(x, lp, lens, H, ft, lin):
    B, S, d = x.shape
    q = lin(x, lp["wq"]).reshape(B, S, H, -1)
    k = lin(x, lp["wk"]).reshape(B, S, H, -1)
    v = lin(x, lp["wv"]).reshape(B, S, H, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(d // H))
    valid = jnp.arange(S)[None, None, None, :] < lens[:, None, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, -2.0e38), axis=-1).astype(ft)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(ft).reshape(B, S, d)


def _logits(G, segments, tokens, lens, H, ft):
    """``segments``: [(bits, stacked quantized layers)], scanned in order."""
    S = tokens.shape[1]
    x = (G["embed"][tokens] + G["pos_embed"][:S][None]).astype(ft)
    for bits, stack in segments:
        lin = functools.partial(_qlin, ft=ft, bits=bits)

        def body(x, lp, lin=lin):
            o = _attention(x, lp["attn"], lens, H, ft, lin)
            x = _ln(x + lin(o, lp["attn"]["wo"]), lp["ln1"], ft)
            h = _gelu(lin(x, lp["ffn"]["w1"]).astype(jnp.float32)).astype(ft)
            return _ln(x + lin(h, lp["ffn"]["w2"]), lp["ln2"], ft), None
        x, _ = jax.lax.scan(body, x, stack)
    h = _ln(x, G["final_norm"], ft)
    pooled = jnp.tanh(_flin(h[:, 0], G["pooler"], ft).astype(jnp.float32))
    return _flin(pooled, G["classifier"], ft).astype(jnp.float32)


def reference_answers(config: dict, prepared: tuple, token_lists: list, *,
                      dtype: str = "float32", block: int = 16) -> np.ndarray:
    """Logits (n, labels) of the quantized model for each token list, in
    blocks of ``block`` rows padded to a power of two (at least 64) with
    the padded keys masked."""
    G, segs = prepared
    H, ft = config["num_attention_heads"], jnp.dtype(dtype)
    bits = [b for b, _ in segs]              # static: one program per grid
    run = jax.jit(lambda G, stacks, toks, lens: _logits(
        G, list(zip(bits, stacks)), toks, lens, H, ft))
    out = np.zeros((len(token_lists), config["num_labels"]), np.float32)
    order = sorted(range(len(token_lists)), key=lambda i: len(token_lists[i]))
    for b in range(0, len(order), block):
        idx = order[b:b + block]
        S = max(64, 1 << (max(len(token_lists[i]) for i in idx) - 1)
                .bit_length())
        toks = np.zeros((block, S), np.int32)
        lens = np.ones(block, np.int32)
        for r, i in enumerate(idx):
            toks[r, :len(token_lists[i])] = token_lists[i]
            lens[r] = len(token_lists[i])
        res = run(G, [st for _, st in segs], jnp.asarray(toks),
                  jnp.asarray(lens))
        out[idx] = np.asarray(res)[:len(idx)]
    return out


# ------------------------------------------------------------ numbers
def numbers(program: np.ndarray, reference: np.ndarray) -> dict:
    """Per answer, the largest logit gap over the sample's largest
    reference logit. Two summaries are compared. The median sees a fault
    in most answers (the control); the mean also sees a gross fault in a
    few of them, such as one bucket or one row of each group answered
    wrong. The widest gap is not compared: a W4A4 network turns one
    activation code flipped by rounding into a gap as large as the
    logits, so it cannot tell a sound run from the control (PERF.md,
    section 2). A non-finite answer makes every number infinite."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    scale = max(float(np.max(np.abs(reference))), 1e-30)
    dev = np.max(np.abs(program - reference), axis=-1) / scale
    if not np.all(np.isfinite(dev)):
        return {"median_dev": float("inf"), "mean_dev": float("inf"),
                "max_dev": float("inf"), "label_agree": 0.0}
    return {"median_dev": float(np.median(dev)),
            "mean_dev": float(np.mean(dev)),
            "max_dev": float(np.max(dev)),
            "label_agree": float(np.mean(program.argmax(-1)
                                         == reference.argmax(-1)))}


def check(config: dict, seed: int, reqs: list, *, control: str = "") -> dict:
    """The numbers for a sample of served classify requests. With
    ``control`` (a dtype name) the reference computed in that dtype takes
    the program's place."""
    tokens = [r.arrival.tokens for r in reqs]
    prepared = prepare(config, seed)
    ref = reference_answers(config, prepared, tokens)
    if control:
        prog = reference_answers(config, prepared, tokens, dtype=control)
    else:
        prog = np.stack([np.asarray(r.result, np.float32).reshape(-1)
                         for r in reqs])
    if prog.shape != ref.shape:
        return {name: float("inf") for name in COMPARED}
    return numbers(prog, ref)
