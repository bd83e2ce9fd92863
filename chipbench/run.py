"""One run of one cell.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of the checkout, on a machine that holds the chips the cell
asks for. Earlier lines of the output are JSON records of what the run saw
(``setup``, ``chunks``, ``check``, ``sweep``, ``unread``: see the README); the
LAST line is the result::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}}        # and "breakdown" with --trace 1

With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones. Without a TPU, or with fewer chips than
the cell asks for, nothing is printed on the standard output and the exit
code is 1.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()   # as near the process's start as Python lets us

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench import cells, measure  # noqa: E402

#: where a traced run writes; inside the checkout, listed in .gitignore
TRACE_DIR = cells.ROOT / ".chipbench_trace"


def peak_bytes(device, resident) -> int:
    """The most a chip held at one time. On this runtime the arrays
    (``bytes_in_use``) and what the runtime sets aside for the programs'
    temporaries (``bytes_reserved``) are disjoint parts of ``bytes_limit``,
    and ``peak_bytes_in_use`` alone reads 1.75 GB where the GPT-2 step
    needs 13: so the sum of the two peaks. Where the arrays peak in set-up
    and the reservation in the window (a train cell's reference holds its
    own copy of the weights while it is trained) the two do not fall
    together, and the driver gives the arrays held when the window opened
    (``resident``): then that plus the peak reservation, or the arrays' own
    peak if that is more."""
    stats = device.memory_stats() or {}
    arrays = stats.get("peak_bytes_in_use", 0)
    reserved = stats.get("peak_bytes_reserved", 0)
    if resident is None:
        return arrays + reserved
    return max(arrays, resident + reserved)


def per_layer_values(cell, context):
    """The cell's per-layer metrics as their readers give them. A reader
    that finds nothing to read returns None: its metric is left out of the
    line, and named on an ``unread`` line and on the standard error."""
    values, unread = {}, []
    for metric in cell.per_layer:
        read, args = cells.load_reader(metric["name"])
        value = read(context, **args)
        if value is None:
            unread.append(metric["name"])
        else:
            values[metric["name"]] = {"value": value, "unit": metric["unit"]}
    if unread:
        measure.emit({"event": "unread", "metrics": unread})
        measure.fail(f"{cell.name}: nothing to read for {unread}")
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.resolve(cells.load_benchmark(), args.workload)

    import pytorch_distributed_tpu.distributed as dist

    dist.initialize_jax_distributed()   # one process: a no-op, as in the examples
    import jax

    from pytorch_distributed_tpu.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        measure.fail(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
        return 1
    # sub-second programs (the engine's small ones, eager helpers) would
    # otherwise be compiled anew in every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = enable_compile_cache()
    used = devices[:cell.chips]
    measure.emit({"event": "start", "workload": cell.name, "seed": args.seed,
                  "seconds": args.seconds, "trace": args.trace,
                  "compile_cache_dir": cache_dir,
                  "imports_s": time.perf_counter() - T0})

    driver = cells.load_driver(cell.traffic["kind"])
    result = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                        used, str(TRACE_DIR))
    if result.why_incorrect:
        measure.emit({"event": "incorrect", "why": result.why_incorrect})

    peak = max(peak_bytes(d, result.resident_bytes) for d in used)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed}
    if args.trace:
        from chipbench import trace_reduce

        reduced = result.context["trace"]
        line["metrics"] = per_layer_values(cell, result.context)
        device["busy_s"] = trace_reduce.busy_seconds(reduced)
        device["window_s"] = trace_reduce.window_seconds(reduced)
        line["breakdown"] = {
            "device_ops": [list(kv) for kv in trace_reduce.top_ops(reduced)],
            "idle_gaps": [list(kv) for kv in
                          trace_reduce.longest_idle_by_span(reduced)],
        }
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = dict(result.end_to_end, setup_s=result.setup_end - T0)
        line["metrics"] = {name: {"value": values[name], "unit": units[name]}
                           for name in units}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
