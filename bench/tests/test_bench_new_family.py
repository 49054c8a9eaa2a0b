"""A family added as one module is taken by the harness with no edit: a
serving run of a multi-head latent attention (MLA) configuration, at the
registry's deepseek-v2-lite-16b smoke widths with the experts left out,
through ``run_cell`` on the host with the look for a chip skipped.  Its
``kv_bytes_mismatch`` check holds the engine's per-stream cache bytes to
the family's own count of the latent cache, and fails under the count of
another family (LLaMA's K and V per head)."""

from __future__ import annotations

import pytest

from bench import run as R
from bench.peaks import PEAKS
from bench.tests import mla_family
from bench.tests.test_bench_serve_faults import SECONDS, small_mix


def mla_conf():
    """The serving settings and limits of ds7b-prefill-burst; the model at
    the registry's smoke widths, its keys as DeepSeek-V2-Lite publishes them."""
    from repro.configs import get_smoke_config

    s = get_smoke_config(mla_family.ARCH)
    conf = R.load_config("deepseek-7b-serve")
    conf.update(arch=mla_family.ARCH, reference="mla_family")
    conf["model"] = {
        "hidden_size": s.d_model, "num_attention_heads": s.n_heads,
        "num_key_value_heads": s.n_kv_heads, "intermediate_size": s.d_ff,
        "num_hidden_layers": s.n_layers, "vocab_size": s.vocab_size,
        "kv_lora_rank": s.mla.kv_lora_rank, "q_lora_rank": None,
        "qk_nope_head_dim": s.mla.qk_nope_dim, "qk_rope_head_dim": s.mla.qk_rope_dim,
        "v_head_dim": s.mla.v_head_dim, "rope_theta": s.rope_theta, "rms_norm_eps": 1e-6,
        "torch_dtype": "float32",
    }
    conf["serve"]["max_len"] = 160
    conf["limits"]["sample_tokens"] = 60
    return conf


@pytest.mark.parametrize("count", ["mla", "llama"])
def test_an_mla_family_added_as_one_module_is_taken(monkeypatch, count):
    monkeypatch.setattr(R, "load_reference", lambda conf: mla_family)
    if count == "llama":
        llama = R.BENCH_DIR / "reference" / "llama.py"
        monkeypatch.setattr(mla_family, "cache_bytes_per_token",
                            R.load_module(llama).cache_bytes_per_token)
    man = R.load_manifest()
    cell = R.find_cell(man, "ds7b-prefill-burst")
    res = R.run_cell(cell, mla_conf(), small_mix(), R.metrics_for(man, cell["name"], False),
                     3_000_000_023, SECONDS, False, require_chip=False,
                     peaks=PEAKS["TPU v5 lite"], compile_cache=False)
    checks = res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if count == "mla":
        assert res["correct"], checks
        assert checks["kv_bytes_mismatch"]["value"] == 0
        assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    else:
        assert not res["correct"]
        assert checks["kv_bytes_mismatch"]["value"] > 0
