"""Chip smoke — the training and serving main paths, once, on the TPU.

    python3 chip_smoke.py             # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # one host, four chips (run by hand)

One process drives everything through the entry points a user calls
(``initialize_jax_distributed`` -> ``init_device_mesh`` -> ``Trainer`` ->
``AsyncRunner``; ``InferenceEngine`` -> ``Scheduler``), at the full widths
of the models the repo supports, in the bf16 the example scripts pick on a
TPU. Weights and data are random, made from ``--seed``.

One chip, three phases:
  * train GPT-2 125M (12 layers, 768 wide, 12 heads, vocab 50257, T=1024),
    FSDP strategy on the one-chip mesh, AdamW, a fixed batch of 8;
  * train ResNet-50 at ImageNet shape (batch 128, 224 px), DataParallel,
    SGD momentum;
  * serve GPT-2 125M (``max_len`` 1024): six requests of different prompt
    lengths through four slots of the continuous-batching scheduler, once
    with the slotted cache and once with the paged one. Every greedy token
    is held to the uncached forward by teacher forcing (the oracle of
    tests/test_serving.py): it must be the reference's argmax, or lie
    within one bf16 ulp (2^-8) of the reference's logit range below it —
    the three programs round differently, and with random weights the top
    two of 50257 logits now and then sit closer than that (first chip run:
    103 of 104 tokens exact, the other 3e-4 of the range away). Slotted
    must equal paged up to the first such tie.

Four chips (``--chips 4``), and nothing else: GPT-2 125M under
FullyShardedDataParallel on ``init_device_mesh((1, 4), ("dp", "fsdp"))``
and ResNet-50 under DataParallel on a 4-device ``dp`` mesh, each compared
with the same model, seed and global batch on a one-device mesh in the
same process: shards on four distinct devices, the collectives
``collective_signature()`` promises in the compiled step, loss parity.

Every phase prints one JSON line; a phase that fails raises, and the run
ends non-zero. The LAST line is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It refuses anything but a TPU: on the CPU the last line says
``"ok": false`` and the exit code is 1. Compile seconds are printed per
phase, so a second run in the same directory shows the persistent cache
(``compile_cache.enable_compile_cache``) hitting.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
import warnings

GPT2_BATCH, GPT2_STEPS = 8, 6
RESNET_BATCH, RESNET_PX, RESNET_STEPS = 128, 224, 12
SERVE_SLOTS = 4
SERVE_PROMPT_LENS = (5, 17, 60, 200, 700, 33)   # buckets 8, 32, 64, 256, 1024
SERVE_NEW_TOKENS = (16, 12, 20, 16, 16, 24)
#: bf16 carries 8 significant bits, and one ulp of it (2^-8) is the unit of
#: both tolerances below.
#:  * Loss parity across meshes. A change of mesh changes the summation
#:    order of the gradient reduction (and of the BatchNorm statistics),
#:    and the step compiled for the TPU reduces gradients ON THE WIRE in
#:    bf16 (ResNet-50 DP: 55.6 MB of all-reduce for 102 MB of fp32
#:    parameters) where one device rounds the whole-batch gradient once;
#:    either flips individual bf16 roundings downstream. Every loss of the
#:    4-device series must stay within one ulp of the FIRST loss from the
#:    1-device series — of the first loss, because the loss on a memorized
#:    batch falls toward zero and a ratio to a vanishing loss measures
#:    nothing. Measured on four v5e chips: at most 6.7e-5 (GPT-2, 6 steps)
#:    and 3.8e-4 (ResNet-50, 12 steps). dryrun.py holds fp32 to 1e-3.
#:  * Greedy tokens against the uncached forward: see ``_serve_gpt2``.
BF16_ULP = 2.0 ** -8


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _require(ok, message: str) -> None:
    if not ok:
        raise RuntimeError(message)


class _Compiles:
    """What XLA compiled so far, from ``jax.monitoring``'s own events:
    seconds inside compile-or-load-from-cache, how many programs, and how
    many of them the persistent cache served."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == self._COMPILE:
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def since(self, mark=(0.0, 0, 0)) -> dict:
        return {
            "compile_s": round(self.seconds - mark[0], 2),
            "programs_compiled": self.programs - mark[1],
            "cache_hits": self.cache_hits - mark[2],
        }

    def mark(self):
        return (self.seconds, self.programs, self.cache_hits)


def _peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


# -- training ---------------------------------------------------------------
def _layout_facts(state, placed, mesh) -> dict:
    """Where the state and the batch live on a multi-device mesh: every
    sharded array has one shard of 1/n of its bytes on each of n distinct
    devices, every replicated array one copy on each, and no array as
    large as a parameter replica sits on the first device alone."""
    import gc

    import jax
    import jax.tree_util as jtu

    n = mesh.size()
    devices = set(mesh.devices.flat)
    # first, before anything below makes per-shard views: what is alive
    # on the first device only (an earlier phase's garbage collected)
    gc.collect()
    replica = sum(x.nbytes for x in jtu.tree_leaves(state.params))
    first = mesh.devices.flat[0]
    alone = [a.nbytes for a in jax.live_arrays()
             if a.sharding.device_set == {first}]
    _require(all(b < replica for b in alone),
             f"an array of {max(alone, default=0)} bytes sits on device "
             f"{first.id} alone (a parameter replica is {replica} bytes)")
    facts = {"sharded_leaves": 0, "replicated_leaves": 0,
             "sharded_bytes": 0, "replicated_bytes": 0}
    for path, leaf in jtu.tree_leaves_with_path((state, placed)):
        shards = leaf.addressable_shards
        on = {s.device for s in shards}
        _require(on == devices,
                 f"{jtu.keystr(path)} lives on {sorted(d.id for d in on)}, "
                 f"not on the {n} devices of the mesh")
        if leaf.sharding.is_fully_replicated:
            facts["replicated_leaves"] += 1
            facts["replicated_bytes"] += leaf.nbytes
            continue
        _require(all(s.data.nbytes * n == leaf.nbytes for s in shards),
                 f"{jtu.keystr(path)} is not split into {n} equal shards: "
                 f"{[s.data.shape for s in shards]} of {leaf.shape}")
        facts["sharded_leaves"] += 1
        facts["sharded_bytes"] += leaf.nbytes
    facts["bytes_on_first_device_alone"] = sum(alone)
    return facts


def _compiled_step_facts(name, state, placed, runner, strategy) -> dict:
    """From the compiled pipelined step itself (the graftir surface):
    donation of every state leaf realized in ``input_output_alias``, and —
    across devices — the tensor-grade collectives the strategy's
    ``collective_signature()`` promises: a gradient reduction, and
    parameter all-gathers under FSDP or none under DP, and under FSDP no
    collective on an activation (``"activations": "local"``: everything on
    the wire is a parameter, a gradient or a shard of one, bar the
    embedding's row exchange: an all-to-all of the looked-up rows and the
    gather of the token ids). Families the signature forbids are reported,
    not refused: it was written from the CPU partitioner's output, and the
    TPU's spells a reduce-scatter as a ring of collective-permutes and the
    row exchange as an all-to-all."""
    import jax.tree_util as jtu

    from pytorch_distributed_tpu.analysis.ir import hlo

    _, compiled = runner.step_artifacts(placed)
    text = compiled.as_text()
    n_state = len(jtu.tree_leaves(state))
    aliased = hlo.aliased_param_indices(text)
    missing = sorted(set(range(n_state)) - set(aliased))
    _require(not missing,
             f"{name}: donated state leaves {missing} are not aliased in "
             f"the compiled step — their buffers are copied, not reused")
    facts = {"donated_state_leaves": n_state, "aliased_inputs": len(aliased)}
    if strategy.mesh.size() == 1:
        return facts
    sig = strategy.collective_signature()
    ops = hlo.collective_inventory(text)
    tensor = hlo.summarize_collectives(ops)["tensor"]
    families = set(tensor)
    if sig.get("activations") == "local":
        counts = hlo.parameter_element_counts(
            (leaf.shape for leaf in jtu.tree_leaves(state.params)),
            [strategy.mesh.size()])
        moved = [op.describe()
                 for op in hlo.activation_collectives(ops, counts)
                 if op.family != "all-to-all" and op.dtype != "s32"]
        _require(not moved,
                 f"{name}: the strategy pins activations to the batch "
                 f"layout, but the compiled step moves {moved}")
    _require(not sig["grad_reduce"] or families & hlo.REDUCE_FAMILIES,
             f"{name}: no tensor-grade all-reduce/reduce-scatter in the "
             f"compiled step — gradients are not synchronized ({tensor})")
    gathers = bool(families & hlo.GATHER_FAMILIES)
    _require(gathers == (sig["param_gather"] != "none"),
             f"{name}: strategy promises param_gather="
             f"{sig['param_gather']!r} but the compiled step has "
             f"{'' if gathers else 'no '}tensor-grade all-gathers ({tensor})")
    facts["collectives"] = tensor
    facts["unpromised_families"] = sorted(families & set(sig["forbid"]))
    return facts


def _train(name, model, optimizer, strategy, loss_fn, batch, steps, seed,
           compiles) -> dict:
    """``steps`` optimizer steps on one fixed batch through the pipelined
    runner. Checks: finite loss at every step, lower at the last than at
    the first; one program dispatched per step from one executable;
    nothing compiled after the warm-up step."""
    import jax
    import numpy as np

    from pytorch_distributed_tpu.data import shard_batch_for_mesh
    from pytorch_distributed_tpu.pipeline_exec import AsyncRunner
    from pytorch_distributed_tpu.trainer import Trainer

    t0, c0 = time.perf_counter(), compiles.mark()
    mesh = strategy.mesh
    trainer = Trainer(model, optimizer, strategy, loss_fn=loss_fn,
                      policy="bf16")
    state = trainer.init(jax.random.key(seed), batch)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    placed = shard_batch_for_mesh(batch, mesh, strategy.batch_axes)
    record = {
        "phase": name, "mesh": mesh.shape, "n_params": int(n_params),
        "batch": [list(x.shape) for x in batch], "steps": steps,
        "strategy": type(strategy).__name__, "policy": "bf16",
    }
    if mesh.size() > 1:
        record["layout"] = _layout_facts(state, placed, mesh)
    runner = AsyncRunner(trainer, depth=2, drain_every=steps)
    runner.start(state, placed)
    record.update(_compiled_step_facts(name, state, placed, runner, strategy))
    runner.submit(placed)   # warm-up step: the one compile of the jit path
    runner.sync()
    warm = compiles.mark()
    for _ in range(steps - 1):
        runner.submit(placed)
    dispatches = runner.dispatch_count
    state, hist = runner.finish()
    loss = hist["loss"]
    record.update(
        loss_first=float(loss[0]), loss_last=float(loss[-1]),
        loss=[round(float(x), 5) for x in loss],
        programs_per_step=dispatches / steps,
        executables=runner.executable_count,
        compiled_after_warmup=compiles.since(warm)["programs_compiled"],
        wall_s=round(time.perf_counter() - t0, 2),
        peak_bytes=_peak_bytes(mesh.devices.flat),
        **compiles.since(c0),
    )
    _emit(record)
    _require(len(loss) == steps and np.isfinite(loss).all(),
             f"{name}: loss series {loss} is not {steps} finite values")
    _require(loss[-1] < loss[0],
             f"{name}: loss did not fall ({loss[0]} -> {loss[-1]})")
    _require(dispatches == steps and runner.executable_count == 1,
             f"{name}: {dispatches} dispatches for {steps} steps from "
             f"{runner.executable_count} executables (want one program "
             f"per step from one executable)")
    _require(record["compiled_after_warmup"] == 0,
             f"{name}: {record['compiled_after_warmup']} programs compiled "
             f"after the warm-up step")
    return record


def _gpt2_batch(cfg, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (GPT2_BATCH, cfg.n_positions))
    tokens = tokens.astype(np.int32)
    return tokens, np.roll(tokens, -1, 1)


def _train_gpt2(name, mesh, seed, compiles, cfg=None) -> dict:
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.parallel import FullyShardedDataParallel
    from pytorch_distributed_tpu.trainer import lm_loss

    cfg = cfg or GPT2Config(dtype=jnp.bfloat16)   # the 125M defaults
    return _train(
        name, GPT2(cfg), optax.adamw(3e-4, weight_decay=0.01),
        FullyShardedDataParallel(mesh, min_shard_size=8), lm_loss,
        _gpt2_batch(cfg, seed), GPT2_STEPS, seed, compiles,
    )


def _train_resnet50(name, mesh, seed, compiles, *, batch=RESNET_BATCH,
                    px=RESNET_PX) -> dict:
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_tpu.models import resnet50
    from pytorch_distributed_tpu.parallel import DataParallel
    from pytorch_distributed_tpu.trainer import classification_loss

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((batch, px, px, 3)).astype(np.float32)
    y = rng.integers(0, 1000, batch).astype(np.int32)
    return _train(
        name, resnet50(num_classes=1000, dtype=jnp.bfloat16),
        optax.sgd(0.1, momentum=0.9), DataParallel(mesh),
        classification_loss, (x, y), RESNET_STEPS, seed, compiles,
    )


# -- serving ----------------------------------------------------------------
def _oracle_regret(fwd, params, cfg, prompt, tokens):
    """Teacher forcing on the uncached forward: for each generated token,
    how far the reference's logit for it lies below the reference's own
    maximum at that position (0 = it IS the argmax), in units of the
    reference's logit range there. One forward per request: causal
    attention makes the zero-padded tail invisible."""
    import jax.numpy as jnp
    import numpy as np

    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    buf = np.zeros((1, cfg.n_positions), np.int32)
    buf[0, : len(seq)] = seq
    logits = np.asarray(
        fwd(params, jnp.asarray(buf))[0, len(prompt) - 1: len(seq)],
        np.float32,
    )                                                    # [n_new, V]
    _require(np.isfinite(logits).all(), "reference logits are not finite")
    top = logits.max(-1)
    got = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return (top - got) / (top - logits.min(-1))


def _serve_gpt2(seed, compiles, cfg=None, prompt_lens=SERVE_PROMPT_LENS,
                new_tokens=SERVE_NEW_TOKENS) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.models import GPT2, GPT2Config
    from pytorch_distributed_tpu.serving import (
        InferenceEngine,
        Request,
        Scheduler,
    )

    t0, c0 = time.perf_counter(), compiles.mark()
    cfg = cfg or GPT2Config(dtype=jnp.bfloat16)
    model = GPT2(cfg)
    params = model.init(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in prompt_lens]
    fwd = jax.jit(model.apply)

    record = {"phase": "serve_gpt2_125m", "n_slots": SERVE_SLOTS,
              "max_len": cfg.n_positions, "prompt_lens": list(prompt_lens),
              "new_tokens": list(new_tokens), "dtype": "bfloat16"}
    streams, regrets = {}, {}
    for kind in ("slotted", "paged"):
        t1, c1 = time.perf_counter(), compiles.mark()
        engine = InferenceEngine(
            model, params, n_slots=SERVE_SLOTS, max_len=cfg.n_positions,
            cache_kind=kind, seed=seed,
        )
        sched = Scheduler(engine, emit_events=False)
        for prompt, n in zip(prompts, new_tokens):
            sched.submit(Request(prompt=prompt, max_new_tokens=n))
        done = {f.request_id: f.tokens for f in sched.run()}
        streams[kind] = [done[i] for i in range(len(prompts))]
        _require([len(t) for t in streams[kind]] == list(new_tokens),
                 f"{kind}: stream lengths "
                 f"{[len(t) for t in streams[kind]]} != {list(new_tokens)}")
        regrets[kind] = [
            _oracle_regret(fwd, params, cfg, p, t)
            for p, t in zip(prompts, streams[kind])
        ]
        regret = np.concatenate(regrets[kind])
        stats = sched.stats()
        record[kind] = {
            "tokens_generated": int(stats["tokens_generated"]),
            "decode_steps": int(stats["decode_steps"]),
            "oracle_argmax_matches": int((regret == 0).sum()),
            "oracle_max_regret": float(regret.max()),
            "wall_s": round(time.perf_counter() - t1, 2),
            **compiles.since(c1),
        }
        _require((regret <= BF16_ULP).all(),
                 f"{kind}: {int((regret > BF16_ULP).sum())} greedy tokens "
                 f"lie more than one bf16 ulp of the logit range below the "
                 f"uncached forward's argmax (worst {regret.max():.4f})")
    # Two greedy decoders that meet a tie part there for good, so the two
    # caches are held to each other up to the first tie: where a pair of
    # streams first differs, the reference must rank BOTH tokens within a
    # bf16 ulp of its maximum (same prefix, hence the same reference row).
    parted = []
    for a, b, ra, rb in zip(streams["slotted"], streams["paged"],
                            regrets["slotted"], regrets["paged"]):
        i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        parted.append(i)
        if i is not None:
            _require(max(ra[i], rb[i]) <= BF16_ULP,
                     f"slotted and paged part at token {i} ({a[i]} vs "
                     f"{b[i]}) where the reference has no tie: regrets "
                     f"{ra[i]}, {rb[i]}")
    record.update(
        oracle_matched=True,   # every token within a bf16 ulp, see above
        slotted_equals_paged=streams["slotted"] == streams["paged"],
        slotted_paged_part_at=parted,
        wall_s=round(time.perf_counter() - t0, 2),
        peak_bytes=_peak_bytes(jax.devices()[:1]),
        **compiles.since(c0),
    )
    _emit(record)
    return record


# -- the two runs -----------------------------------------------------------
def run_one_chip(seed, compiles) -> None:
    import jax

    import pytorch_distributed_tpu as ptd

    n = len(jax.devices())
    _train_gpt2("train_gpt2_125m_fsdp",
                ptd.init_device_mesh((1, n), ("dp", "fsdp")), seed, compiles)
    _train_resnet50("train_resnet50_dp",
                    ptd.init_device_mesh((n,), ("dp",)), seed, compiles)
    _serve_gpt2(seed, compiles)


def run_four_chips(seed, compiles) -> None:
    """The path across chips and what it is compared with, nothing else.
    The multi-device run goes first so the peak-memory readings and the
    nothing-on-the-first-device-alone check see it undisturbed."""
    import jax
    import numpy as np

    import pytorch_distributed_tpu as ptd

    devs = jax.devices()
    n = len(devs)
    for name, train, axes, shape_n, shape_1 in (
        ("gpt2_125m_fsdp", _train_gpt2, ("dp", "fsdp"), (1, n), (1, 1)),
        ("resnet50_dp", _train_resnet50, ("dp",), (n,), (1,)),
    ):
        many = train(f"train_{name}_{n}dev",
                     ptd.init_device_mesh(shape_n, axes), seed, compiles)
        ref = train(f"train_{name}_1dev",
                    ptd.init_device_mesh(shape_1, axes, devices=devs[:1]),
                    seed, compiles)
        got, want = np.asarray(many["loss"]), np.asarray(ref["loss"])
        rel = np.abs(got - want) / want[0]
        _emit({"phase": f"parity_{name}",
               "loss_diff_over_first_loss": [float(f"{r:.3g}") for r in rel],
               "allowed": BF16_ULP})
        _require((rel <= BF16_ULP).all(),
                 f"{name}: {n}-device loss series {got} leaves the "
                 f"1-device series {want} by {rel} of the first loss "
                 f"(allowed {BF16_ULP})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train + serve on one chip (default); 4: the "
                         "sharded training path on one four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import pytorch_distributed_tpu.distributed as dist

    dist.initialize_jax_distributed()   # single process: a no-op, as in the examples
    import jax

    from pytorch_distributed_tpu.compile_cache import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or len(devs) != args.chips:
        _emit({"ok": False, "device": device,
               "error": f"needs {args.chips} TPU chip(s), found "
                        f"{len(devs)} {device['platform']} device(s)"})
        return 1
    # the fallbacks this run must not take quietly
    warnings.filterwarnings(
        "error", message=".*topology-aware mesh placement failed")
    warnings.filterwarnings(
        "error", message=".*donated buffers were not usable")
    _emit({"phase": "start", "device": device, "seed": args.seed,
           "compile_cache_dir": enable_compile_cache(),
           "jax": jax.__version__})
    compiles = _Compiles()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args.seed, compiles)
        else:
            run_one_chip(args.seed, compiles)
    except Exception as e:
        traceback.print_exc()
        _emit({"ok": False, "device": device,
               "error": f"{type(e).__name__}: {e}"[:2000]})
        return 1
    _emit({"phase": "total", "wall_s": round(time.perf_counter() - t0, 2),
           **compiles.since()})
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
