"""A run whose timed path is broken underneath has to come out with
``correct`` false: one run per fault a serving cell can have, driven
through ``run.main`` at tiny widths on the CPU with the chip check
skipped."""

import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench.tiny import TINY_MIX, tiny_cell, tiny_conf
from repro.core import comms
from repro.runtime import serving


#: short prompts and long answers, so that each answer's later tokens
#: depend on the earlier ones held in the cache
MIX = {**TINY_MIX, "rate_per_s": 12.0,
       "prompt": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
       "output": {"median": 24, "sigma": 0.3, "min": 12, "max": 32}}


def result(conf, devices):
    conf["serve"]["max_new_tokens"] = 32
    out = io.StringIO()
    with redirect_stdout(out):
        R.main(["--workload", "tiny", "--seed", "77", "--seconds", "2",
                "--trace", "0"], cell=tiny_cell(conf, MIX), devices=devices)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def broken_serve_step(fault):
    make = serving.make_serve_step

    def patched(cfg, run, rules):
        step = make(cfg, run, rules)

        def f(params, cache, tokens):
            logits, new = step(params, cache, tokens)
            if fault == "state_unchanged":       # the cache never moves
                return logits, cache
            half = logits.shape[0] // 2            # half the batch left out
            return logits.at[:half].set(0.0), new
        return f
    return patched


def altered_greedy(greedy):
    calls = [0]

    def f(self, logits):
        tok = greedy(self, logits)
        calls[0] += 1
        if calls[0] % 3 == 0:                 # a token altered where made
            tok = (tok + 1) % self.cfg.vocab_size
        return tok
    return f


def test_sound_run_is_correct():
    res = result(tiny_conf("starcoder2-15b-l10"), jax.devices("cpu")[:1])
    assert res["correct"] is True


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_decode_step_is_caught(monkeypatch, fault):
    monkeypatch.setattr(serving, "make_serve_step", broken_serve_step(fault))
    res = result(tiny_conf("starcoder2-15b-l10"), jax.devices("cpu")[:1])
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_altered_token_is_caught(monkeypatch):
    monkeypatch.setattr(serving.ServingEngine, "_greedy",
                        altered_greedy(serving.ServingEngine._greedy))
    res = result(tiny_conf("starcoder2-15b-l10"), jax.devices("cpu")[:1])
    assert res["correct"] is False


def test_exchange_between_chips_left_out_is_caught(monkeypatch):
    def local_only(self, x, w, **_):
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(x.dtype)

    monkeypatch.setattr(comms.CommContext, "matmul_all_reduce", local_only)
    conf = tiny_conf("internlm2-20b-tp4", mesh=[1, 4], prefill_batch=2)
    res = result(conf, jax.devices("cpu")[:4])
    assert res["correct"] is False
    assert np.isfinite(res["checks"]["logit_gap"]["value"])
