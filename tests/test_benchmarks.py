"""Smoke coverage for the benchmark matrix harness (SURVEY §6): the
harness itself must stay runnable — the driver and BASELINE.md depend on
its JSON shape."""
import numpy as np
import pytest

from benchmarks.matrix import (
    CONFIGS,
    _decode_bench,
    _multihost_bench,
    _spec_decode_bench,
    config5_elastic_restart,
)


def test_config5_elastic_restart_recovers():
    res = config5_elastic_restart()
    assert res["recovered_after_worker_death"] is True
    assert res["total_wall_s_incl_restart"] < 60


def test_config1_smoke_shape():
    res = CONFIGS[1](smoke=True)
    assert res["images_per_sec"] > 0
    assert np.isfinite(res["step_ms"])


def test_config6_from_disk_smoke():
    res = CONFIGS[6](smoke=True)
    assert res["from_disk_images_per_sec"] > 0
    assert res["loader_only_images_per_sec"] > 0
    assert res["synthetic_images_per_sec"] > 0


def test_config7_from_disk_smoke():
    res = CONFIGS[7](smoke=True)
    assert res["from_disk_tokens_per_sec"] > 0
    assert res["loader_only_tokens_per_sec"] > 0


def _tiny_decode_model():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config(vocab_size=128, n_positions=64, n_embd=32,
                     n_layer=2, n_head=4)
    model = GPT2(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    return model, variables, cfg


def test_config9_decode_harness_smoke():
    """The decode + spec-decode measurement harnesses stay runnable and
    report sane numbers, at a shape small enough for tier-1."""
    model, variables, cfg = _tiny_decode_model()
    r = _decode_bench(model, variables, cfg.vocab_size, 2, 32, 8, 6, 4)
    assert r["tokens_per_sec"] > 0
    assert r["per_token_p99_ms"] >= r["per_token_p50_ms"] > 0
    s = _spec_decode_bench(model, variables, cfg.vocab_size, 2, 40, 8, 6,
                           4, 2, 1)
    assert s["tokens_per_sec"] > 0
    assert 0.0 <= s["accept_rate"] <= 1.0
    # one verify per step emits >= 1 token/slot: forwards/token <= 1
    assert 0 < s["target_forwards_per_token"] <= 1.0
    # the two are reciprocals, reported rounded to 3 and 4 places
    # (matrix._spec_decode_bench): their product is 1 to within what that
    # rounding allows, whatever the accept pattern of the run
    m, f = s["mean_tokens_per_step"], s["target_forwards_per_token"]
    assert m * f == pytest.approx(1.0, abs=m * 0.5e-4 + f * 0.5e-3 + 1e-9)


@pytest.mark.multihost
def test_config9_multihost_harness_smoke():
    """The multi-host serving measurement harness (router + in-process
    host workers over a HashStore) stays runnable at tier-1 shape."""
    model, variables, cfg = _tiny_decode_model()
    r = _multihost_bench(model, variables, cfg.vocab_size, 2, 2, 32, 8,
                         6, 3, 4)
    assert r["platform"]  # provenance stamp (report.py depends on it)
    assert r["tokens_per_sec"] > 0
    assert r["request_p99_ms"] >= r["request_p50_ms"] > 0
    assert r["routed"] == r["n_requests"] == 3
    assert r["rebalances"] == 0
    assert sum(r["per_host_routed"].values()) == 3


def test_report_renders_multihost_and_graftlint():
    """The generated BASELINE.md block carries the multihost row (with
    its platform provenance) and the static-analysis state."""
    from benchmarks import report

    text = report.render()
    assert "Multi-host serving (router + " in text
    assert "[platform=" in text
    lint = report._graftlint_summary()
    assert lint is not None and lint["rules_run"]
    assert report._fmt_graftlint(lint) in text


@pytest.mark.slow
def test_config9_decode_full():
    """The full config-#9 sweep (slot curve + speculative variants) —
    multi-second, so tier-1 runs the harness smoke above instead."""
    res = CONFIGS[9](smoke=True)
    assert res["name"] == "gpt2_decode"
    assert res["platform"]  # provenance stamp (report.py depends on it)
    assert len(res["sweeps"]) >= 2
    for s in res["sweeps"]:
        assert s["tokens_per_sec"] > 0
        assert s["per_token_p99_ms"] >= s["per_token_p50_ms"] > 0
    # throughput must grow with the slot count (batched decode amortizes)
    assert (res["sweeps"][-1]["tokens_per_sec"]
            > res["sweeps"][0]["tokens_per_sec"])
    assert len(res["spec_sweeps"]) >= 2
    for s in res["spec_sweeps"]:
        assert 0.0 <= s["accept_rate"] <= 1.0
        # the acceptance headline: speculation must beat one forward
        # per token by a clear margin on this fixed-seed shape
        assert s["target_forwards_per_token"] < 0.8
    mh = res["multihost"]
    assert mh["platform"] == res["platform"]
    assert mh["tokens_per_sec"] > 0
    assert mh["routed"] == mh["n_requests"]
