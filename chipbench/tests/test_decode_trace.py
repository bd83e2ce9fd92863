"""The readers PR 33 brought (``decode_time_by_scope``,
``decode_kernel_roofline``) on hand-made traces: an operation counts only
inside a run of the decode program, under the section the decode program's
text gives its instruction; where the program has no such registry entry,
span or stat, the readers return None and do not raise."""

from chipbench import decode_trace
from chipbench.program_trace import HostSpan
from chipbench.readers import decode_kernel_roofline, decode_time_by_scope
from chipbench.trace_reduce import DeviceTrace, Reduced

NAMES = {
    "fusion.1": "jit(decode_fn)/Xing4/hc/layer_0_attn_hc/mul",
    "custom-call.2": "jit(decode_fn)/Xing4/mla/layer_0_attn/jit(_kernel_read)"
                     "/latent_attention_read/pallas_call",
    "ragged-dot-none.3": "ragged-dot-none",
}
CONFIG = {"kv_lora_rank": 512, "qk_rope_head_dim": 64, "num_hidden_layers": 6}


def _context(modules, ops, spans=(), names=NAMES):
    reduced = Reduced(
        devices=[DeviceTrace(ordinal=0, ops=ops, modules=modules,
                             async_ops=[])],
        spans=[], window=(0.0, 10.0))
    return {"trace": reduced, "decode_op_names": names,
            "program_spans": list(spans),
            "counters": {"device_kind": "TPU v5 lite", "config": CONFIG}}


def _decode_spans(kv_rows):
    out = []
    for i, rows in enumerate(kv_rows):
        step = HostSpan("sched.step", i, i + 0.9, {"kv_rows": rows})
        out += [step, HostSpan("engine.decode", i + 0.1, i + 0.8, {},
                               parent=step)]
    return out


def test_an_operation_counts_only_inside_a_run_of_the_decode_program():
    modules = [("jit_decode_fn(7)", 1.0, 2.0), ("jit_prefill_fn(9)", 3.0, 5.0),
               ("jit_decode_fn(7)", 6.0, 7.0)]
    ops = [("%fusion.1 = f32[48] fusion(...)", 1.1, 1.2),
           ("%fusion.1 = bf16[1,8192] fusion(...)", 3.1, 4.1),  # prefill's
           ("%custom-call.2 = bf16[48,32,512] custom-call(...)", 6.0, 6.5),
           ("%ragged-dot-none.3 = f32[192,1024] custom-call(...)", 6.5, 6.9),
           ("%copy.9 = f32[4] copy(...)", 6.9, 7.0)]            # no op_name
    context = _context(modules, ops)
    found, runs = decode_trace.decode_ops(context)
    assert runs == 2 and len(found) == 3
    ms = decode_time_by_scope.read
    assert abs(ms(context, include="/hc/") - 1e3 * 0.1 / 2) < 1e-6
    assert abs(ms(context, include="/mla/") - 1e3 * 0.5 / 2) < 1e-6
    assert abs(ms(context, include="moe/|ragged-dot") - 1e3 * 0.4 / 2) < 1e-6
    assert ms(context, include="/nothing/") is None


def test_roofline_share_is_bytes_that_must_move_over_time_over_peak():
    modules = [("jit_decode_fn(7)", 0.0, 1.0), ("jit_decode_fn(7)", 1.0, 2.0)]
    ops = [("%custom-call.2 = bf16[48,32,512] custom-call(...)", 0.0, 0.001),
           ("%custom-call.2 = bf16[48,32,512] custom-call(...)", 1.0, 1.001)]
    context = _context(modules, ops, _decode_spans([10_000, 30_000]))
    share = decode_kernel_roofline.read(
        context, kernel="latent_attention_read", bytes="latent_read_bytes")
    moved = (10_000 + 30_000) * 576 * 2 * 6
    assert abs(share - 100 * moved / 0.002 / 819e9) < 1e-6
    assert 0 < share < 100


def test_a_program_without_the_registry_span_or_stat_reads_nothing():
    modules = [("jit_decode_fn(7)", 0.0, 1.0)]
    ops = [("%custom-call.2 = bf16[48,32,512] custom-call(...)", 0.0, 0.001)]
    args = dict(kernel="latent_attention_read", bytes="latent_read_bytes")
    # no text of the decode program (the parent of the PR that adds it)
    empty = _context(modules, ops, _decode_spans([10]), names={"x": "y"})
    assert decode_time_by_scope.read(empty, include="/mla/") is None
    assert decode_kernel_roofline.read(empty, **args) is None
    # the kernel ran but no span carries ``kv_rows``
    assert decode_kernel_roofline.read(_context(modules, ops), **args) is None
    # no trace at all
    assert decode_time_by_scope.read({"trace": None}, include="/mla/") is None
    assert decode_kernel_roofline.read({"trace": None}, **args) is None
