"""A whole serving run on the host at a small size, with the look for a chip
skipped: a sound run is correct, and each fault planted in the timed path
underneath makes ``correct`` come out false.  The fp8 control, put in the
program's place, fails the logit-gap comparison where the program passes.

The small model keeps the served configuration's logit scale: its
initializer range grows with 1/sqrt(hidden_size) from the published 0.02
at 4096, so its logits spread as the cell's do and the cell's limit
applies."""

from __future__ import annotations

import math

import numpy as np

from bench import run as R
from bench import traffic
from bench.peaks import PEAKS

SECONDS = 2.0


def small_conf():
    conf = R.load_config("deepseek-7b-serve")
    d = 128
    conf["model"].update(hidden_size=d, intermediate_size=2 * d, num_attention_heads=4,
                         num_key_value_heads=4, num_hidden_layers=2, vocab_size=1024,
                         initializer_range=0.02 * math.sqrt(4096 / d))
    conf["serve"]["max_len"] = 160
    conf["limits"]["sample_tokens"] = 60
    return conf


def small_mix():
    mix = traffic.load_mix("prefill_burst")
    mix.update(rate_per_s=6.0,
               prompt={"median": 64, "sigma": 0.5, "min": 32, "max": 128, "round_to": 32},
               output={"median": 8, "sigma": 0.6, "min": 4, "max": 16})
    return mix


def run_small(seed=3_000_000_019, conf=None):
    man = R.load_manifest()
    cell = R.find_cell(man, "ds7b-prefill-burst")
    return R.run_cell(cell, conf or small_conf(), small_mix(), R.metrics_for(man, cell["name"], False),
                      seed, SECONDS, False, require_chip=False, peaks=PEAKS["TPU v5 lite"],
                      compile_cache=False)


def test_a_sound_run_is_correct():
    res = run_small()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"ttft_p95_ms", "itl_p95_ms", "output_tokens_per_s", "setup_s"} <= set(res["metrics"])
    assert res["checks"]["logit_gap"]["value"] <= res["checks"]["logit_gap"]["limit"]


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.serve.engine import Engine

    select = Engine._select_tokens

    def altered(self, logits):
        out = select(self, logits)
        return (out + 1) % logits.shape[-1]

    monkeypatch.setattr(Engine, "_select_tokens", altered)
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_a_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    import repro.serve.engine as engine

    decode = engine.decode_step

    def stale(cfg, params, cache, tokens, pos, **kw):
        logits, _ = decode(cfg, params, cache, tokens, pos, **kw)
        return logits, cache

    monkeypatch.setattr(engine, "decode_step", stale)
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_per_stream_bytes_that_disagree_with_the_client_are_caught(monkeypatch):
    from repro.serve.engine import Engine

    est = Engine._estimate_kv_bytes_per_token
    monkeypatch.setattr(Engine, "_estimate_kv_bytes_per_token", lambda self: est(self) // 2)
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["kv_bytes_mismatch"]["value"] > 0


def test_the_fp8_control_fails_where_the_program_passes():
    """The reference in fp8, in the program's place: the gap of the token it
    puts first, read at the positions served.  The cell's depth and head
    size, a quarter of a chip's width and a small vocabulary, so that fp8's
    rounding compounds through the layers as it does in the cell."""
    conf = small_conf()
    conf["model"].update(hidden_size=256, intermediate_size=688, num_attention_heads=2,
                         num_key_value_heads=2, num_hidden_layers=16, vocab_size=4096,
                         initializer_range=0.02 * math.sqrt(4096 / 256))
    ref = R.load_reference(conf)
    import jax

    m = conf["model"]
    w = ref.make_weights(m, jax.random.key(7))
    rng = np.random.default_rng(7)
    limit = conf["limits"]["logit_gap"]
    readings = []
    for _ in range(3):
        prompt = rng.integers(0, m["vocab_size"], 96, dtype=np.int32)
        seq = list(prompt)
        served = []
        for _ in range(24):  # greedy tokens of the reference itself
            logits = ref.logits_at(m, w, np.asarray(seq, np.int32), [len(seq) - 1],
                                   pad_to=160, rows_to=24)
            served.append(int(np.argmax(logits[0])))
            seq.append(served[-1])
        readings.append(ref.served_gaps(m, w, prompt, served, pad_to=160, rows_to=24,
                                        control=True))
    assert max(r["served"] for r in readings) == 0.0
    assert min(r["control"] for r in readings) > limit
