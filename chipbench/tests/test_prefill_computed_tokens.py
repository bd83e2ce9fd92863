"""``prefill_computed_tokens_pct`` (PR 51) is data alone: its file names a
reader the benchmark already has, which reads the token positions that the
prefills' tokenwise loops ran over the positions of their buckets from the
``pdt.engine.prefill`` spans of the window, and nothing from a program
whose spans do not say (the parent of the PR that brought ``n_computed``)."""

from chipbench import cells, program_trace, trace_reduce
from chipbench.readers import program_span_ratio
from chipbench.tests import handmade_program

NAME = "prefill_computed_tokens_pct"


def _context(prefills):
    profile = handmade_program.profile({"/host:CPU": {"python3": [
        ("cb.window", 0, 100)] + [
        ("pdt.engine.prefill", 10 + 20 * i, 15, stats)
        for i, stats in enumerate(prefills)]}})
    return {"trace": trace_reduce.from_profile(profile),
            "program_spans": program_trace.spans_of_profile(profile)}


def test_the_metric_is_a_data_file_over_a_reader_that_is_there():
    read, args = cells.load_reader(NAME)
    assert read is program_span_ratio.read
    assert args == {"span": "engine.prefill", "num": "n_computed",
                    "den": "bucket", "scale": 100.0}
    entry, = [m for m in cells.load_benchmark()["per_layer"]
              if m["name"] == NAME]
    assert entry["layer"] == "models and peaks"
    assert entry["moves"] == "serve_ttft_p95_ms"
    assert (entry["better"], entry["source"]) == ("lower", "program_counter")
    assert entry["workloads"] == [
        "k-exaone-236b-a23b.serve-mixed-len",
        "kimi-linear-48b-a3b.serve-long-answer",
        "mimo-v2.5.serve-code-agent"]


def test_it_is_a_ratio_of_sums_over_the_windows_prefills():
    read, args = cells.load_reader(NAME)
    context = _context([
        {"bucket": 32768, "n_real": 22646, "n_computed": 24576},
        {"bucket": 4096, "n_real": 2709, "n_computed": 4096},
        {"bucket": 2048, "n_real": 300, "n_computed": 2048}])
    assert read(context, **args) == 100.0 * 30720 / 38912


def test_it_reads_nothing_from_a_span_without_the_count():
    read, args = cells.load_reader(NAME)
    parent = _context([{"bucket": 32768, "n_real": 22646},
                       {"bucket": 4096, "n_real": 2709}])
    assert read(parent, **args) is None
    assert read({"trace": None}, **args) is None
