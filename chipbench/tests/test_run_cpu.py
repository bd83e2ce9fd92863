"""What can be shown without the chip: the command refuses the CPU, and the
two drivers run end to end at tiny shapes that only these tests choose."""

import json
import subprocess
import sys

import pytest

from chipbench import cells

TINY_GPT2 = {
    "family": "gpt2", "vocab_size": 512, "n_positions": 64, "n_embd": 64,
    "n_layer": 2, "n_head": 4, "layer_norm_epsilon": 1e-5,
    "assumed": {"compute_dtype": "float32", "param_dtype": "float32",
                "policy": "fp32",
                "optimizer": {"optax": "adamw",
                              "kwargs": {"learning_rate": 3e-4,
                                         "weight_decay": 0.01}},
                "trained_loss_tolerance": 1e-5},
}
TINY_TRAIN = {
    "kind": "train", "rate_metric": "train_tok_s_chip", "batch": 4,
    "seq_len": 32,
    "strategy": {"class": "FullyShardedDataParallel",
                 "kwargs": {"min_shard_size": 8}},
    "mesh": {"shape": [1, 1], "axes": ["dp", "fsdp"]}, "pool_batches": 3,
    "chunk_steps": 4,
    "trace_rate_chunks": 2, "trace_chunks": 1,
}
TINY_SERVE = {
    "kind": "serve_open_loop", "n_slots": 4, "max_len": 64,
    "cache_kind": "slotted",
    "prompt_len": {"dist": "log_uniform", "min": 4, "max": 16},
    "output_len": {"dist": "log_uniform", "min": 4, "max": 8},
    "arrivals": {"gaps": "exponential_quantiles", "rate_per_s": 30.0},
    "warm_seconds": 0.3, "tail_seconds": 1.0, "drain_seconds_max": 20.0,
    "trace_seconds": 1.0, "base_seed": 1,
}


def test_the_command_refuses_a_machine_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "gpt2-125m.train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result line
    assert "needs 1 TPU chip" in proc.stderr


def test_train_driver_at_a_tiny_size(capsys):
    import jax

    from chipbench.drivers import train

    result = train.run(_tiny_cell(), 2 ** 31 + 7, 0.3, False, jax.devices()[:1], "")
    assert result.correct, result.why_incorrect
    assert result.attempted >= 8 and result.failed == 0
    assert result.end_to_end["train_tok_s_chip"] > 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    chunks = next(l for l in lines if l["event"] == "chunks")
    assert chunks["n"] == len(chunks["times_s"]) >= 2
    assert chunks["min_s"] <= chunks["median_s"] <= chunks["max_s"]
    check = next(l for l in lines if l["event"] == "check")
    # float32 on both sides here: the program trains as the reference does
    assert len(check["reference_rel_diff"]) == 4
    assert max(check["reference_rel_diff"]) < 1e-5
    assert check["loss"][3] < check["loss"][0]   # one batch, three updates
    assert chunks["rate"] == pytest.approx(
        4 * 32 * 4 * chunks["n"] / sum(chunks["times_s"]))
    assert result.end_to_end["train_tok_s_chip"] == chunks["rate"]
    assert check["executables"] == 1 and check["compiled_in_window"] == 0


def _tiny_cell(**traffic):
    return cells.Cell("tiny", 1, "tiny", TINY_GPT2, "tiny",
                      dict(TINY_TRAIN, **traffic), [], [])


def test_an_update_unlike_the_reference_is_caught(monkeypatch, capsys):
    """The reference is trained without its updates, which is what a
    program that dropped its own would look like from the other side: the
    first loss agrees, the losses after an update do not."""
    import jax
    import optax

    from chipbench.drivers import train

    real = train.reference_losses
    monkeypatch.setattr(
        train, "reference_losses",
        lambda task, optimizer, state, batch: real(
            task, optax.adamw(0.0, weight_decay=0.0), state, batch))
    result = train.run(_tiny_cell(), 5, 0.2, False, jax.devices()[:1], "")
    assert not result.correct
    assert "after 0 updates" not in result.why_incorrect
    assert "after 1 updates" in result.why_incorrect
    assert "after 3 updates" in result.why_incorrect


def test_four_devices_hold_a_quarter_of_every_sharded_parameter(capsys):
    import jax

    from chipbench.drivers import train

    four = {"mesh": {"shape": [1, 4], "axes": ["dp", "fsdp"]},
            "params_sharded_over": 4}
    result = train.run(_tiny_cell(**four), 9, 0.2, False, jax.devices()[:4],
                       "")
    assert result.correct, result.why_incorrect
    # the same layout asked of a strategy that replicates is a fault
    replicated = dict(four, strategy={"class": "DataParallel", "kwargs": {}},
                      mesh={"shape": [4], "axes": ["dp"]})
    result = train.run(_tiny_cell(**replicated), 9, 0.2, False,
                       jax.devices()[:4], "")
    assert not result.correct
    assert "parameter bytes are sharded" in result.why_incorrect


def test_a_metric_with_nothing_to_read_is_left_out_and_named(capsys):
    from chipbench import run

    cell = cells.Cell("tiny", 1, "tiny", {}, "tiny", {}, [], [
        {"name": "hbm_gb.tok", "unit": "GB"},
        {"name": "device_idle_pct.tok", "unit": "%"}])
    values = run.per_layer_values(
        cell, {"programs": {"step": {"total": 2e9}}, "trace": None})
    assert values == {"hbm_gb.tok": {"value": 2.0, "unit": "GB"}}
    out = capsys.readouterr()
    assert json.loads(out.out) == {"event": "unread",
                                   "metrics": ["device_idle_pct.tok"]}
    assert "device_idle_pct.tok" in out.err


def test_serve_driver_at_a_tiny_size(capsys):
    import jax

    from chipbench.drivers import serve_open_loop

    config = dict(TINY_GPT2)
    cell = cells.Cell("tiny", 1, "tiny", config, "tiny", TINY_SERVE, [], [])
    result = serve_open_loop.run(cell, 11, 1.0, False, jax.devices()[:1], "")
    assert result.correct, result.why_incorrect
    assert result.attempted == 30 and result.failed == 0
    assert result.end_to_end["serve_ttft_p95_ms"] > 0
    assert result.end_to_end["serve_tpot_p50_ms"] > 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    check = next(l for l in lines if l["event"] == "check")
    assert check["argmax_matches"] == check["checked_tokens"] > 0
    assert next(l for l in lines if l["event"] == "sweep")["unfinished"] == 0
