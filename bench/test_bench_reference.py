"""The float32 reference against the engine, at tiny widths on the CPU, for
both cells' code paths (non-gated GELU and gated SiLU), and its float8
control, which has to fail the comparison."""

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


@pytest.mark.parametrize("name,group", CELLS)
def test_engine_prefill_then_decode_matches_reference(name, group):
    conf = tiny_conf(name, prefill_batch=group)
    built = C.build(conf, 5, jax.devices("cpu")[:1])
    r = C.rng(5, 9)
    prompt = r.integers(0, 256, size=13)
    got, served = engine_logits(built, prompt, 8)
    a = reference.Arch.of(conf)
    tokens = np.zeros(reference.QBLOCK, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    tokens[:len(seq)] = seq
    rows = len(prompt) - 1 + np.arange(len(served))
    want = np.asarray(reference.logits(built.engine.params, a, tokens,
                                       rows))
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
    built = C.build(conf, 0, jax.devices("cpu")[:1])
    loop.warm_up(built.engine, 256, C.rng(0, 4))
    for seed in (0, 1):
        if seed:
            built.engine.params = C.make_weights(built.tmpl, seed, 64)
        run = loop.serve(built.engine, Traffic(TINY_MIX, seed, 256),
                         preroll_s=0.3, seconds=1.5)
        built.engine.take_undone()
        got = reference.compare(built.engine.params, conf, run.reqs,
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
