"""Where one benchmark run's set-up goes: ``chipbench.run`` as it is, with
JAX's own monitoring events summed by phase and every line of its output
stamped with the seconds since this process began (PERF.md section 5,
"where a warm set-up goes"; PR 48).

    python3 chip_setup_phases.py --workload <cell> --seed <n> --seconds 51 --trace 0

The run's lines come first, each with ``t_s``; the last line is
``{"event": "phases", ...}``: for every duration event of ``jax.monitoring``
its count and its summed seconds (``jaxpr_trace_duration`` is tracing,
``jaxpr_to_mlir_module_duration`` is lowering, a Mosaic kernel's among it;
``backend_compile_duration`` is the compile or, warm, the load from the
persistent cache, whose own part is ``cache_retrieval_time_sec``). The
run's ``setup_s`` is short of the benchmark's own by JAX's import, which
happens here before ``chipbench.run`` starts its clock.
"""

import json
import sys
import time

T0 = time.perf_counter()

import jax  # noqa: E402

from chipbench import measure, run  # noqa: E402

PHASES = {}


def _duration(event, seconds, **_):
    count, total = PHASES.get(event, (0, 0.0))
    PHASES[event] = (count + 1, total + seconds)


def main():
    jax.monitoring.register_event_duration_secs_listener(_duration)
    emit = measure.emit

    def stamped(record):
        emit({**record, "t_s": time.perf_counter() - T0})

    measure.emit = stamped
    for module in list(sys.modules.values()):   # drivers bind ``emit`` by name
        if getattr(module, "emit", None) is emit:
            module.emit = stamped
    code = run.main()
    print(json.dumps({"event": "phases", "t_s": time.perf_counter() - T0,
                      "jax_import_s": run.T0 - T0, "phases": {
                          name.rsplit("/", 1)[-1]: [count, seconds]
                          for name, (count, seconds) in sorted(PHASES.items())
                      }}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
