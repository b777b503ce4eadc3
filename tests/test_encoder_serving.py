"""Encoder serving tests (DESIGN.md §14).

The encoder path's contract is the PR-5 exactness property transplanted to
bidirectional models: an EncodeRequest's result is a pure function of its
tokens — never of bucket padding, batch composition, or what other traffic
shares the engine. The headline tests assert BYTE-identical results between
the engine's batched bucketed forward and a direct single-row
``bert_classify_logits``/``bert_encode`` call at the exact length, for both
an int8 and an int4 W4A4 deployed plan (the quantized paths where a
batching bug would also change numerics).

Lifecycle tests reuse the generation-side semantics the encode path shares:
deadline shedding and cancellation through the same scheduler, on a
``VirtualClock`` so timing is exact. The decode-engine ``score`` task
(prompt log-likelihood through the chunked prefill path) gets the same
batch-independence treatment.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.policy import QuantPolicy
from repro.deploy import ExecutionPlan, deploy
from repro.models import api
from repro.models.bert import (bert_classify_logits, bert_encode, bert_pool,
                               init_bert_classifier, tinybert_config)
from repro.serving import (EncodeRequest, GenerationRequest, ServingEngine,
                           VirtualClock)

KEY = jax.random.PRNGKey(0)
_CACHE = {}


def _encoder_model(mode):
    """Deployed TinyBERT classifier under a mode='encoder' plan, cached."""
    if mode not in _CACHE:
        cfg = tinybert_config(num_classes=2, layers=2, d=64, heads=4,
                              d_ff=128, vocab=256, name="tinybert-test")
        pol = QuantPolicy(num_layers=cfg.num_layers, mode="int",
                          last_k_int4=cfg.num_layers if mode == "int4" else 0)
        plan = ExecutionPlan.build(
            cfg, pol, backend="reference", mode="encoder", prefill_batch=4,
            **({"act_bits": 4} if mode == "int4" else {}))
        _CACHE[mode] = deploy(init_bert_classifier(cfg, 2, KEY), plan)
    return _CACHE[mode]


def _decoder_model():
    if "decoder" not in _CACHE:
        cfg = reduced(get_config("stablelm-3b")).replace(act="gelu")
        pol = QuantPolicy(num_layers=cfg.num_layers, mode="int",
                          last_k_int4=cfg.num_layers)
        plan = ExecutionPlan.build(cfg, pol, backend="reference", act_bits=4)
        _CACHE["decoder"] = (deploy(api.init_model(cfg, KEY), plan), cfg)
    return _CACHE["decoder"]


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lens]


# --------------------------------------------- batched == direct, bitwise
def _direct(model, prompts, bucket, task):
    """The reference the engine must be byte-faithful to: ONE jitted
    ``bert_classify_logits``/``bert_encode`` call on the same padded batch
    the engine's group runs (public API, no engine machinery)."""
    toks = np.zeros((len(prompts), bucket), np.int32)
    lens = np.array([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p

    @jax.jit
    def fwd(params, toks, lens):
        h, _ = bert_encode(params, model.plan, toks, lengths=lens)
        embed = bert_pool(params, h)
        logits = (embed @ params["classifier"]["w"]
                  + params["classifier"]["b"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return {"classify": logits, "embed": embed, "score": logp[:, 1]}

    return np.asarray(fwd(model.params, jnp.asarray(toks),
                          jnp.asarray(lens))[task])


class _CountedCopy:
    """A device output that counts its host copies under its task's name."""

    def __init__(self, arr, task, copies):
        self._arr, self._task, self._copies = arr, task, copies

    def __array__(self, dtype=None, copy=None):
        self._copies[self._task] = self._copies.get(self._task, 0) + 1
        return np.asarray(self._arr, dtype)


def _count_copies(eng):
    """Wrap ``eng._encode_fn`` so every output's host copy is counted."""
    copies, real = {}, eng._encode_fn

    def counted_fn(bucket, n):
        fn = real(bucket, n)

        def call(*a):
            return {k: _CountedCopy(v, k, copies) for k, v in fn(*a).items()}
        return call
    eng._encode_fn = counted_fn
    return copies


@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("tasks", [
    ("classify",) * 4, ("embed",) * 4, ("score",) * 4,
    ("classify", "embed", "score", "classify"),
], ids=["classify", "embed", "score", "mixed"])
def test_engine_batched_matches_direct_forward(mode, tasks):
    """One mixed-length group through the engine == the direct batched
    forward, byte-for-byte (int8 AND int4 plans) — the engine's grouping,
    bucketing, row routing and result slicing add nothing numerically.
    The group copies each output it asked for to the host once, and never
    one that no request in it asked for."""
    model = _encoder_model(mode)
    eng = ServingEngine(model, slots=4, max_len=64, clock=VirtualClock())
    copies = _count_copies(eng)
    # lengths 5..8 share one bucket (8), so all four run as ONE group of 4
    prompts = _prompts(256, (5, 6, 7, 8), seed=mode == "int4")
    handles = [eng.submit_encode(EncodeRequest(tokens=p, task=t))
               for p, t in zip(prompts, tasks)]
    eng.run_until_drained()

    summary = eng.metrics.summary()
    assert summary["spans"]["serve/encode/group"]["n"] == 1
    assert copies == {t: 1 for t in tasks}
    assert summary["encode_arrays_read"] == len(set(tasks))
    for i, (p, t, h) in enumerate(zip(prompts, tasks, handles)):
        res = h.result()
        assert res.finish_reason == "done"
        np.testing.assert_array_equal(np.asarray(res.value),
                                      _direct(model, prompts, 8, t)[i])
        # and the exact-length unbatched eager forward agrees numerically
        logits, _ = bert_classify_logits(model.params, model.plan,
                                         jnp.asarray(p[None]))
        if t == "classify":
            ref = np.asarray(logits)[0]
            np.testing.assert_allclose(np.asarray(res.value), ref,
                                       rtol=2e-5, atol=1e-7)


def test_padding_rows_are_bit_exact():
    """The model-level property the serving path is built on: a row padded
    to a bucket with its keys masked (``lengths=``) is bit-identical to the
    unpadded forward — bidirectional attention never sees the zero tail."""
    model = _encoder_model("int4")
    p = _prompts(256, (5,), seed=11)[0]
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = p
    got, _ = bert_classify_logits(model.params, model.plan,
                                  jnp.asarray(padded),
                                  lengths=jnp.asarray([5]))
    want, _ = bert_classify_logits(model.params, model.plan,
                                   jnp.asarray(p[None]))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_results_independent_of_batch_composition():
    """A request's result does not depend on which other requests share its
    group — neither their content, their lengths, nor their order."""
    model = _encoder_model("int4")
    p1, p2, p3 = _prompts(256, (5, 8, 6), seed=7)

    def run(batch):
        eng = ServingEngine(model, slots=4, max_len=64,
                            clock=VirtualClock())
        hs = {id(p): eng.submit_encode(
                  EncodeRequest(tokens=p, task="classify")) for p in batch}
        eng.run_until_drained()
        return np.asarray(hs[id(p1)].result().value)

    base = run([p1, p2])
    np.testing.assert_array_equal(base, run([p1, p3]))   # different neighbor
    np.testing.assert_array_equal(base, run([p2, p1]))   # different order


# ----------------------------------------------------- lifecycle semantics
def test_encode_deadline_shed_on_virtual_clock():
    model = _encoder_model("int8")
    clock = VirtualClock()
    eng = ServingEngine(model, slots=2, max_len=64, clock=clock)
    h = eng.submit_encode(EncodeRequest(tokens=np.arange(1, 6),
                                        deadline_s=0.05))
    clock.advance(0.1)             # past the admission deadline
    eng.engine_step()
    assert h.finished and h.finish_reason == "shed"
    assert h.result().value is None
    assert not eng.scheduler.has_work


def test_encode_cancel_while_queued():
    model = _encoder_model("int8")
    eng = ServingEngine(model, slots=2, max_len=64, clock=VirtualClock())
    seen = []
    h = eng.submit_encode(EncodeRequest(tokens=np.arange(1, 6)),
                          on_result=lambda rid, v: seen.append((rid, v)))
    assert h.cancel()
    assert h.finished and h.finish_reason == "cancelled"
    assert seen == [(h.rid, None)]
    assert not eng.scheduler.has_work
    assert not h.cancel()          # already terminal


def test_encode_priority_orders_admission():
    """Higher-priority encode requests admit first when slots are scarce."""
    model = _encoder_model("int8")
    eng = ServingEngine(model, slots=1, max_len=64, clock=VirtualClock())
    order = []
    hs = [eng.submit_encode(EncodeRequest(tokens=np.arange(1, 5),
                                          priority=pr),
                            on_result=lambda rid, v: order.append(rid))
          for pr in (0, 5, 1)]
    eng.run_until_drained()
    assert order == [hs[1].rid, hs[2].rid, hs[0].rid]


# --------------------------------------------------------- task validation
def test_bad_task_and_empty_tokens_rejected():
    with pytest.raises(ValueError, match="task"):
        EncodeRequest(tokens=np.arange(3), task="generate")
    model = _encoder_model("int8")
    eng = ServingEngine(model, slots=2, max_len=8, clock=VirtualClock())
    with pytest.raises(ValueError, match="empty"):
        eng.submit_encode(EncodeRequest(tokens=np.array([], np.int32)))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit_encode(EncodeRequest(tokens=np.arange(1, 12)))


def test_encoder_engine_rejects_generation_submit():
    model = _encoder_model("int8")
    eng = ServingEngine(model, slots=2, max_len=64, clock=VirtualClock())
    with pytest.raises(ValueError):
        eng.submit(GenerationRequest(prompt=np.arange(1, 5),
                                     max_new_tokens=2))


# ------------------------------------------------- decoder 'score' service
def test_decoder_engine_serves_score_only():
    (model, cfg) = _decoder_model()
    eng = ServingEngine(model, slots=2, max_len=64, clock=VirtualClock())
    for task in ("classify", "embed"):
        with pytest.raises(ValueError, match="score"):
            eng.submit_encode(EncodeRequest(tokens=np.arange(1, 5),
                                            task=task))


def test_decoder_score_is_batch_independent_loglikelihood():
    """score == prompt log-likelihood, and (causal ⇒) independent of batch
    composition and of the generation traffic sharing the engine."""
    (model, cfg) = _decoder_model()
    prompts = _prompts(cfg.vocab_size, (4, 7, 11), seed=3)

    def run(batch, with_gen=False):
        eng = ServingEngine(model, slots=4, max_len=64,
                            clock=VirtualClock())
        hs = [eng.submit_encode(EncodeRequest(tokens=p, task="score"))
              for p in batch]
        if with_gen:
            eng.submit(GenerationRequest(prompt=np.arange(1, 6),
                                         max_new_tokens=3))
        eng.run_until_drained()
        # the decoder's forward returns 'score' alone: one copy a group
        s = eng.metrics.summary()
        assert s["encode_arrays_read"] == s["spans"]["serve/encode/group"]["n"]
        return [np.asarray(h.result().value) for h in hs]

    together = run(prompts, with_gen=True)
    for p, got in zip(prompts, together):
        assert got.shape == ()
        assert np.isfinite(got) and got <= 0.0    # it is a log-probability
        alone, = run([p])
        np.testing.assert_array_equal(got, alone)


@pytest.mark.parametrize("arch", ["tinybert4", "bert-base"])
def test_serve_encoder_mode_takes_arch(arch, tmp_path, capsys):
    """``serve --mode encoder --arch`` builds that registry model (reduced
    here), deploys it W4A4 and serves an encode burst."""
    from repro.deploy import DeployedModel
    from repro.launch import serve
    args = ["--mode", "encoder", "--reduced", "--requests", "2",
            "--slots", "2", "--act-bits", "4", "--export", str(tmp_path)]
    if arch != "tinybert4":       # tinybert4 is the encoder default
        args += ["--arch", arch]
    serve.main(args)
    assert "encoder burst: 2 requests (2 done)" in capsys.readouterr().out
    plan = DeployedModel.load(str(tmp_path)).plan
    assert plan.cfg.name == arch and plan.mode == "encoder"
    assert plan.cfg == reduced(get_config(arch))
