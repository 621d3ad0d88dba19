"""Device time by named scope (bench/scopes.py): on a trace recorded from
a CPU run of a tiny jitted scan, on hand-made nested operations, in the
new readers on made-up records, and against the serve engine's compiled
programs, so that a renamed scope fails here and not only on the chip."""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import run as harness
from bench import scopes as S
from bench import trace as T
from bench.counts import dense_decoder, dense_sublayers

GLM4 = harness.load("configs", "glm4-9b-8L")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cpu_op_line(plane, line):
    """XLA's threads on the CPU: the client's, and the pool's that run a
    scan body's operations as often as not; together they stand for one
    device."""
    return plane == "/host:CPU" and line.startswith(("tf_XLAPjRtCpuClient",
                                                     "tf_XLAEigen"))


def scanned(x, w):
    def body(c, wi):
        with jax.named_scope("attn_qkv"):
            h = c @ wi
        with jax.named_scope("ffn"):
            h = jnp.tanh(h) + c
        return h, None
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(body, x, w)
    return x.sum()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(scanned)
    x, w = jnp.ones((256, 256), jnp.float32), jnp.ones((4, 256, 256), jnp.float32)
    f(x, w).block_until_ready()
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(T.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                f(x, w).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    text = f.lower(x, w).compile().as_text()
    return T.read(T.newest_xplane(logdir), ("step",), device_line=cpu_op_line), text


def test_recorded_scan_charges_both_scopes(recorded):
    tr, text = recorded
    got = S.span_scopes(tr, "step", text)
    assert got["attn_qkv"] > 0 and got["ffn"] > 0
    assert sum(got.values()) == pytest.approx(tr.busy_in("step"), rel=1e-9)


def test_existing_reduction_unchanged(recorded):
    tr, text = recorded
    before = (tr.busy_s(), tr.busy_in("step"), tr.idle_gaps(), tr.device_ops(),
              tr.window_s())
    S.span_scopes(tr, "step", text)
    S.self_time(tr.ops[next(iter(tr.ops))])
    assert (tr.busy_s(), tr.busy_in("step"), tr.idle_gaps(), tr.device_ops(),
            tr.window_s()) == before


def test_self_time_counts_each_nanosecond_once():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 60), ("c", 45, 50),
           ("d", 90, 120), ("e", 130, 140)]
    by = {}
    for name, s, e in S.self_time(ops):
        by[name] = by.get(name, 0) + e - s
    assert by == {"while": 50, "a": 20, "b": 15, "c": 5, "d": 30, "e": 10}
    assert sum(by.values()) == T.length(T.union((s, e) for _, s, e in ops))
    assert S.self_time([]) == []


def test_span_scopes_on_hand_made_trace():
    text = "\n".join([
        "ENTRY %main {",
        '  %while.3 = (s32[], bf16[8,32]{1,0}) while(%t), metadata={op_name="jit(f)/layers/while"}',
        '  %fusion.7 = bf16[32,64]{1,0:T(8,128)(2,1)} fusion(%p), kind=kOutput, '
        'metadata={op_name="jit(f)/layers/while/body/closed_call/ffn/add"}',
        "  ROOT %copy.2 = bf16[8,32]{1,0} copy(%x)",
        "}"])
    tr = T.Trace(ops={"/device:TPU:0": [
        ("%while.3 = (s32[]{:T(128)}, bf16[8,32]{1,0:T(8,128)}) while((s32[], bf16[8,32]) %t)", 10, 60),
        ("%fusion.7 = bf16[32,64]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} %p), kind=kOutput", 20, 40),
        ("%copy.2 = bf16[8,32]{1,0} copy(bf16[8,32]{1,0} %x)", 60, 70),
        ("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %q)", 70, 75),         # another program's
        ("%fusion.7 = bf16[32,64]{1,0} fusion(bf16[8]{0} %p)", 200, 210)]},   # outside the span
        spans=[(T.WINDOW, 0, 300), ("decode", 5, 80), ("token_fetch", 72, 80)])
    got = S.span_scopes(tr, "decode", text)
    assert got == pytest.approx({"layers": 30e-9, "ffn": 20e-9,
                                 S.UNSCOPED: 10e-9, S.OTHER: 5e-9})
    assert sum(got.values()) == pytest.approx(tr.busy_in("decode"))


def test_instruction_parsing():
    assert S.result_type("(s32[]{:T(128)}, /*index=1*/bf16[8,32]{1,0:T(8,128)(2,1)S(1)}) while(") \
        == "(s32[],bf16[8,32])"
    assert S.result_type("bf16[1,32,8192,2,128]{4,3,2,1,0:T(2,128)(2,1)} fusion(") \
        == "bf16[1,32,8192,2,128]"
    assert S.scope_of_path("jit(f)/layers/while/body/attn_core/while/body/dot_general") == "attn_core"
    assert S.scope_of_path("jit(f)/transpose") == S.UNSCOPED
    table = {"dot.4": ("f32[2]", "ffn")}
    assert S.scope_of_op("dot.4", table) == "ffn"                   # the CPU's bare name
    assert S.scope_of_op("%dot.4 = f32[2]{0} dot(f32[2]{0} %a)", table) == "ffn"
    assert S.scope_of_op("%dot.4 = f32[3]{0} dot(f32[3]{0} %a)", table) == S.OTHER
    assert S.scope_of_op("end: dot.9", table) == S.OTHER


def record(scopes, batches=2, steps=3, batch=32, prompt=256):
    trace = T.Trace(ops={"/device:TPU:0": [("op", 0, 10**9)]},
                    spans=[(T.WINDOW, 0, 2 * 10**9), ("decode", 0, 10**9)])
    times = [{"times": [0.0] * (steps + 1)} for _ in range(batches)]
    return SimpleNamespace(trace=trace, batches=times, config=GLM4, batch=batch,
                           prompt=prompt, new_tokens=128, peaks=PEAKS, scopes=scopes)


def read(name, rec):
    return harness.read_metric(name, rec)


def test_prefill_readers():
    rec = record({"prefill": {"attn_core": 0.5, "attn_qkv": 0.1, "attn_out": 0.1,
                              "ffn": 0.2, "head": 0.1, "norm": 9.0}, "decode": {}},
                 batch=4, prompt=4096)
    attn = 2 * dense_sublayers.prefill_attention_flops(GLM4, 4, 4096)
    mm = 2 * dense_sublayers.prefill_matmul_flops(GLM4, 4, 4096)
    assert read("prefill_attn_mfu", rec) == pytest.approx(100 * attn / 0.5 / 197e12)
    assert read("prefill_matmul_mfu", rec) == pytest.approx(100 * mm / 0.5 / 197e12)


def test_decode_readers():
    rec = record({"prefill": {}, "decode": {
        "attn_cache": 0.2, "layers:cache": 0.25, "layers:weights": 0.02,
        "layers": 0.03, "unscoped:cache": 0.1, S.UNSCOPED: 0.01,
        S.OTHER: 0.005, "attn_core": 0.1, "attn_qkv": 0.05, "attn_out": 0.05,
        "ffn": 0.1, "head": 0.05}})
    assert read("decode_cache_share", rec) == pytest.approx(55.0)   # of 1 s busy
    kv = 2 * sum(dense_sublayers.decode_kv_read_bytes(GLM4, 32, 256 + k)
                 for k in range(1, 4))
    assert read("decode_attn_hbm_roofline", rec) == pytest.approx(
        100 * kv / 819e9 / 0.1)
    w = 6 * dense_sublayers.decode_weight_bytes(GLM4)
    assert read("decode_weights_hbm_roofline", rec) == pytest.approx(
        100 * w / 819e9 / 0.27)


def test_scan_slices_go_to_what_they_move():
    """On a hand-made decode trace, the scan's slice of a weight and its
    slices of the cache are both ``layers``; the weight slice is charged to
    the products' time and not to cache movement, the cache slices and
    XLA's copy of the stacked cache the other way round."""
    text = "\n".join([
        "ENTRY %main {",
        '  %while.3 = (s32[], bf16[2,8,64,2,16]{4,3,2,1,0}) while(%t), '
        'metadata={op_name="jit(f)/layers/while"}',
        '  %dslice.1 = bf16[1,64,64]{2,1,0} fusion(%w), kind=kLoop, '
        'metadata={op_name="jit(f)/layers/while/body/dynamic_slice"}',
        '  %dslice.2 = bf16[1,8,64,2,16]{4,3,2,1,0} fusion(%k), kind=kLoop, '
        'metadata={op_name="jit(f)/layers/while/body/dynamic_slice"}',
        '  %bitcast.5 = bf16[8,64,2,16]{3,1,2,0} fusion(%dslice.2), kind=kLoop, '
        'metadata={op_name="jit(f)/layers/while/body/squeeze"}',
        '  %fusion.7 = bf16[8,64]{1,0} fusion(%p), kind=kOutput, '
        'metadata={op_name="jit(f)/layers/while/body/closed_call/attn_out/dot_general"}',
        '  %s32.1 = s32[] add(%i, %one), metadata={op_name="jit(f)/layers/while/body/add"}',
        "  %copy.9 = bf16[2,8,64,2,16]{4,3,2,1,0} copy(%c)",
        "  ROOT %copy.10 = bf16[1,64,64]{2,1,0} copy(%w2)",
        "}"])
    dev = [("%while.3 = (s32[], bf16[2,8,64,2,16]{4,3,2,1,0}) while(%t)", 0, 100),
           ("%dslice.1 = bf16[1,64,64]{2,1,0} fusion(%w)", 0, 10),
           ("%dslice.2 = bf16[1,8,64,2,16]{4,3,2,1,0} fusion(%k)", 10, 40),
           ("%bitcast.5 = bf16[8,64,2,16]{3,1,2,0} fusion(%dslice.2)", 40, 60),
           ("%fusion.7 = bf16[8,64]{1,0} fusion(%p)", 60, 75),
           ("%s32.1 = s32[] add(%i, %one)", 75, 77),
           ("%copy.9 = bf16[2,8,64,2,16]{4,3,2,1,0} copy(%c)", 100, 150),
           ("%copy.10 = bf16[1,64,64]{2,1,0} copy(%w2)", 150, 155)]
    tr = T.Trace(ops={"/device:TPU:0": dev},
                 spans=[(T.WINDOW, 0, 200), ("decode", 0, 160)])
    kinds = S.moved_shapes({"blocks": {"wo": jax.ShapeDtypeStruct((2, 64, 64), jnp.bfloat16),
                                       "norm": jax.ShapeDtypeStruct((2, 64), jnp.float32)},
                            "embed": jax.ShapeDtypeStruct((512, 64), jnp.bfloat16)},
                           {"k": jax.ShapeDtypeStruct((2, 8, 64, 2, 16), jnp.bfloat16)})
    got = S.span_scopes(tr, "decode", text, kinds)
    assert got == pytest.approx({"layers:weights": 10e-9, "layers:cache": 50e-9,
                                 "attn_out": 15e-9, "layers": 25e-9,
                                 "unscoped:cache": 50e-9, "unscoped:weights": 5e-9})
    assert sum(got.values()) == pytest.approx(tr.busy_in("decode"))
    rec = record({"prefill": {}, "decode": got})
    rec.trace = tr
    assert read("decode_cache_share", rec) == pytest.approx(100 * 100 / 155)
    w = 6 * dense_sublayers.decode_weight_bytes(GLM4)
    assert read("decode_weights_hbm_roofline", rec) == pytest.approx(
        100 * w / 819e9 / 30e-9)


def test_moved_shapes():
    kinds = S.moved_shapes(
        {"a": {"wq": jnp.zeros((3, 4, 8)), "g": jnp.zeros((3, 4))},
         "head": jnp.zeros((4, 9))},
        {"pos0": {"k": jax.ShapeDtypeStruct((3, 2, 16, 1, 8), jnp.bfloat16)}})
    assert kinds == {(3, 4, 8): S.WEIGHTS, (1, 4, 8): S.WEIGHTS, (4, 8): S.WEIGHTS,
                     (3, 2, 16, 1, 8): S.CACHE, (1, 2, 16, 1, 8): S.CACHE,
                     (2, 16, 1, 8): S.CACHE}
    assert S.shape_of("bf16[1,32,8192]") == (1, 32, 8192)
    assert S.shape_of("s32[]") == ()
    assert S.shape_of("(s32[],bf16[8])") is None


NEW = ("prefill_attn_mfu", "prefill_matmul_mfu", "decode_cache_share",
       "decode_attn_hbm_roofline", "decode_weights_hbm_roofline")


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("scopes", [None, {"prefill": {}, "decode": {}},
                                    {"prefill": {"other": 1.0}, "decode": {"other": 1.0}},
                                    {"prefill": None, "decode": None}])
def test_readers_without_their_scopes_read_nothing(name, scopes):
    assert read(name, record(scopes)) is None


def test_sublayer_counts_add_up():
    for B, P in ((4, 4096), (1, 17)):
        assert (dense_sublayers.prefill_attention_flops(GLM4, B, P)
                + dense_sublayers.prefill_matmul_flops(GLM4, B, P)
                == dense_decoder.prefill_flops(GLM4, B, P))
    L, d = GLM4["num_hidden_layers"], GLM4["hidden_size"]
    for B, live in ((32, 257), (1, 1)):
        assert (dense_sublayers.decode_weight_bytes(GLM4) + (2 * L + 1) * d * 4
                + dense_sublayers.decode_kv_read_bytes(GLM4, B, live)
                + B * dense_decoder.kv_bytes_per_position(GLM4)
                == dense_decoder.decode_bytes(GLM4, B, live))


def test_engine_programs_carry_every_scope_the_readers_use():
    """The readers' labels are in the serve engine's compiled programs,
    each in the program its reader looks in: the scopes, and the scan's
    slices of the cache and of the weights told apart by their shapes."""
    from bench.metrics import (decode_cache_share, decode_weights_hbm_roofline,
                               prefill_matmul_mfu)
    from repro.models.common import SCOPES

    config = dict(GLM4, hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  num_hidden_layers=2, vocab_size=512)
    rec = SimpleNamespace(config=config, batch=2, prompt=16, new_tokens=8,
                          max_seq=32)
    texts, kinds = S.programs(rec)
    found = {}
    for span, text in texts.items():
        table = S.instruction_scopes(text)
        found[span] = {S.label_of_op(name, table, kinds) for name in table}
    assert {"attn_core", *prefill_matmul_mfu.MATMULS} <= found["prefill"]
    assert {"attn_core", "layers:cache", "layers:weights",
            *decode_weights_hbm_roofline.READS_WEIGHTS[:4]} <= found["decode"]
    assert set(decode_cache_share.MOVES_CACHE) & found["decode"] >= {
        "attn_cache", "layers:cache"}
    scopes = {label.split(":")[0] for label in found["prefill"] | found["decode"]}
    assert scopes >= set(SCOPES)


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    """A program whose compiled text carries no scope (one built before
    the scopes, or cached without its metadata) gives no scopes at all,
    so no reader takes its unscoped time for cache movement."""
    text = "ENTRY %main {\n  ROOT %copy.2 = bf16[8,32]{1,0} copy(%x)\n}"
    monkeypatch.setattr(S, "programs",
                        lambda rec: ({"prefill": text, "decode": text},
                                     {(8, 32): S.CACHE}))
    rec = record(None)
    del rec.scopes
    assert S.of(rec) is None
    assert read("decode_cache_share", rec) is None


def test_a_span_mostly_of_other_programs_reads_nothing(monkeypatch):
    """Where more than ``OTHER_MAX`` of a span's time matches no
    instruction of the compiled copy, that copy is not the program that
    ran, and the span gives no scopes."""
    text = "\n".join([
        "ENTRY %main {",
        '  ROOT %copy.2 = bf16[8,32]{1,0} copy(%x), metadata={op_name="jit(f)/layers/x"}',
        "}"])
    monkeypatch.setattr(S, "programs",
                        lambda rec: ({"prefill": text, "decode": text}, {}))
    rec = record(None)
    del rec.scopes
    rec.trace = T.Trace(ops={"/device:TPU:0": [
        ("%copy.2 = bf16[8,32]{1,0} copy(%x)", 0, 980),
        ("%fusion.1 = f32[4]{0} fusion(%y)", 980, 1000),     # 2 % other
        ("%copy.2 = bf16[8,32]{1,0} copy(%x)", 2000, 2999),
        ("%fusion.1 = f32[4]{0} fusion(%y)", 2999, 3000)]},  # 0.1 % other
        spans=[(T.WINDOW, 0, 4000), ("decode", 0, 1000), ("prefill", 2000, 3000)])
    got = S.of(rec)
    assert got["decode"] is None
    assert got["prefill"] == pytest.approx({"layers": 999e-9, S.OTHER: 1e-9})


def test_a_program_from_before_the_scopes_loads_and_reads_nothing():
    """Against a program with no vocabulary and no ``ServeEngine.lower``
    (the commit before the scopes), the scope readers load and report
    nothing, so a traced run there still ends with its line."""
    import importlib

    import repro.models.common as common
    from repro.serve import ServeEngine

    vocabulary, lower = common.SCOPES, ServeEngine.lower
    del common.SCOPES, ServeEngine.lower
    try:
        importlib.reload(S)
        assert S.SCOPES == ()
        rec = record(None)
        del rec.scopes
        rec.cell = {}
        for name in NEW:
            assert read(name, rec) is None
    finally:
        common.SCOPES, ServeEngine.lower = vocabulary, lower
        importlib.reload(S)
    assert S.SCOPES == vocabulary
