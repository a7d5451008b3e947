"""The float32 reference of the dense model module against the engine, at
tiny widths on the CPU, for both configurations' code paths (non-gated
GELU and gated SiLU), its float8 control, which has to fail the
comparison, and the comparison's readings as they were before the forward
pass moved into the module."""

import jax
import numpy as np
import pytest

from bench import cell as C
from bench import loop, reference
from bench.tiny import TINY_MIX, tiny_conf
from bench.traffic import Traffic

#: the tiny cells' limit, set as the cells' own are: over five seeds of
#: each tiny cell (12 requests, about 57 tokens compared) the program read
#: 0.000-0.027 and the float8 control 0.125-0.553
TINY_LIMIT = 0.06
CELLS = [("starcoder2-15b-l10", 1), ("internlm2-20b-tp4", 2)]


def engine_logits(built, prompt, n_new):
    """Serve one request and keep the f32 logits the engine sampled from
    at each of its positions."""
    eng = built.engine
    seen = []
    greedy = eng._greedy

    def keep(logits):
        seen.append(np.asarray(logits[:, -1, :eng.cfg.vocab_size],
                               np.float32))
        return greedy(logits)

    eng._greedy = keep
    rid = eng.submit(prompt, n_new)
    while eng.step() is not None:
        pass
    eng._greedy = greedy
    slot = eng.completions[rid].slot
    rows = [seen[0][0]] + [s[slot] for s in seen[1:]]
    return np.stack(rows), eng.completions[rid].tokens


def ref_logits(model, weights, conf, prompt, served):
    """The module's reference logits at each served token's position."""
    tokens = np.zeros(reference.QBLOCK, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    tokens[:len(seq)] = seq
    rows = len(prompt) - 1 + np.arange(len(served))
    return np.asarray(model.logits(weights, conf, tokens, rows))


@pytest.mark.parametrize("name,group", CELLS)
def test_engine_prefill_then_decode_matches_reference(name, group):
    conf = tiny_conf(name, prefill_batch=group)
    model = C.load_model(conf["bench_model"])
    built = C.build(model, conf, 5, jax.devices("cpu")[:1])
    r = C.rng(5, 9)
    prompt = r.integers(0, 256, size=13)
    got, served = engine_logits(built, prompt, 8)
    want = ref_logits(model, built.engine.params, conf, prompt, served)
    # bf16 activations through two layers: a few parts in a thousand of the
    # logits' norm; a wrong position, mask or weight is near 1.4
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    assert err.max() < 2e-2, err
    gap = want.max(axis=1) - want[np.arange(len(served)), served]
    assert gap.max() <= TINY_LIMIT


@pytest.mark.parametrize("name,group", CELLS)
def test_float8_control_fails_the_comparison(name, group):
    conf = tiny_conf(name, prefill_batch=group)
    conf["check"]["requests"] = 12
    model = C.load_model(conf["bench_model"])
    built = C.build(model, conf, 0, jax.devices("cpu")[:1])
    loop.warm_up(built.engine, 256, C.rng(0, 4))
    for seed in (0, 1):
        if seed:
            built.engine.params = C.make_weights(built.tmpl, seed, 64)
        run = loop.serve(built.engine, Traffic(TINY_MIX, seed, 256),
                         preroll_s=0.3, seconds=1.5)
        built.engine.take_undone()
        got = reference.compare(model, built.engine.params, conf, run.reqs,
                                C.rng(seed, 3), control=True)
        assert got["tokens"] >= 12
        assert got["logit_gap"] <= TINY_LIMIT < got["control_gap"], got


def test_sample_takes_the_longest():
    class R:
        def __init__(self, n, p):
            self.tokens, self.prompt = [0] * n, [0] * p

    done = [R(3, 9), R(8, 2), R(5, 5), R(8, 4), R(1, 1)]
    picked = reference.sample(done, 3, np.random.default_rng(0))
    assert picked[0] is done[3]
    assert len(picked) == 3 and len({id(p) for p in picked}) == 3


#: per configuration, what the comparison read before the forward pass
#: moved into the dense module, on the requests below served to the end
#: (seed 5): the program's widest gap, the float8 control's, and the sum
#: and absolute sum of the reference logits of the first request
BEFORE = {"starcoder2-15b-l10": (0.0, 0.018092870712280273,
                                 -93.31067607879231, 1693.2059191272274),
          "internlm2-20b-tp4": (0.0, 0.31425559520721436,
                                -43.711469143629074, 1658.6310534754302)}


@pytest.mark.parametrize("name,group", CELLS)
def test_comparison_reads_as_before_the_move(name, group):
    conf = tiny_conf(name, prefill_batch=group)
    model = C.load_model(conf["bench_model"])
    eng = C.build(model, conf, 5, jax.devices("cpu")[:1]).engine
    r = C.rng(5, 9)
    reqs = []
    for n, out in ((13, 8), (20, 6), (7, 8), (31, 5)):
        q = loop.Req(0.0, r.integers(0, 256, size=n), out)
        q.rid = eng.submit(q.prompt, out)
        reqs.append(q)
    while eng.step() is not None:
        pass
    for q in reqs:
        q.tokens = eng.completions[q.rid].tokens
    got = reference.compare(model, eng.params, conf, reqs, C.rng(5, 3),
                            control=True)
    lg = ref_logits(model, eng.params, conf, reqs[0].prompt, reqs[0].tokens)
    assert (got["logit_gap"], got["control_gap"],
            float(lg.astype(np.float64).sum()),
            float(np.abs(lg).astype(np.float64).sum())) == BEFORE[name]
