"""The readers of the program's own spans and scopes, on a hand-made trace
with known answers and on a short piece of a trace recorded on a TPU v5e."""
import os

import pytest

from bench import program_trace as PT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "program_trace_small.json.gz")
LOOP = "jit(chunk_step)/while/body/closed_call"


def _hand_made():
    """ns. A prefill step 10-1000 and a decode step 2000-3000, each with its
    phases; a ``while`` holding scoped fusions; eager programs inside the
    decode span."""
    spans = [["serve.schedule", 0, 10],
             ["serve.prefill", 10, 990],
             ["serve.inputs", 20, 30], ["serve.kv_gather", 50, 50],
             ["serve.forward", 100, 700], ["serve.kv_scatter", 800, 50],
             ["serve.sample", 850, 150],
             ["serve.schedule", 1990, 10],
             ["serve.decode", 2000, 1000],
             ["serve.inputs", 2000, 50], ["serve.kv_gather", 2050, 100],
             ["serve.forward", 2150, 500], ["serve.kv_scatter", 2650, 100],
             ["serve.sample", 2750, 250]]
    ops = [  # [start, dur, scope]; the while holds the next four
        [100, 600, "jit(chunk_step)/while"],
        [110, 100, f"{LOOP}/mlp/falcon.combine_a/add"],
        [210, 300, f"{LOOP}/mlp/falcon.lcma_gemm/dot_general"],
        [510, 50, f"{LOOP}/mlp/falcon.combine_h/convert_element_type"],
        [560, 100, f"{LOOP}/attn/falcon.gemm/dot_general"],
        [660, 40, f"{LOOP}/attn/exp"],
        [720, 30, ""],                                  # after the while
        [810, 20, "jit(scatter)/scatter"],
        # decode: a gather 2060-2100, the step 2160-2600, a scatter 2660-2700,
        # the logits slice 2760-2770
        [2060, 40, "jit(gather)/gather"],
        [2160, 440, "jit(decode_step)/while"],
        [2160, 440, f"{LOOP}/attn/falcon.gemm/dot_general"],
        [2660, 40, "jit(scatter)/scatter"],
        [2760, 10, "jit(slice)/slice"]]
    modules = [["jit_chunk_step(1)", 100, 610], ["jit_scatter(2)", 810, 20],
               ["jit_gather(3)", 2060, 40], ["jit_decode_step(4)", 2160, 440],
               ["jit_scatter(2)", 2660, 40], ["jit_slice(5)", 2760, 10]]
    leaves = PT._leaves([tuple(o) for o in ops])
    busy = PT.TR._merged([(s, s + d) for s, d, _ in ops])
    return PT.Program(sorted(spans, key=lambda s: s[1]), leaves, busy, modules)


def test_containers_are_left_out_and_each_op_counts_once():
    pg = _hand_made()
    scopes = [sc for _, _, sc in pg.ops]
    assert "jit(chunk_step)/while" not in scopes
    assert "jit(decode_step)/while" not in scopes     # equal interval: once
    assert len(pg.ops) == 11
    assert sum(d for s, d, _ in pg.ops if 2160 <= s < 2600) == 440


def test_stage_split_is_self_time_per_prefill_step():
    split = PT.stage_split(_hand_made(), "prefill")
    assert split["falcon.combine_a"] == pytest.approx(100e-6)
    assert split["falcon.lcma_gemm"] == pytest.approx(300e-6)
    assert split["falcon.combine_h"] == pytest.approx(50e-6)
    assert split["falcon.gemm"] == pytest.approx(100e-6)   # inside attn
    assert split["attn"] == pytest.approx(40e-6)
    assert split["rest"] == pytest.approx((30 + 20) * 1e-6)
    assert sum(split.values()) == pytest.approx((600 - 10 + 30 + 20) * 1e-6)
    pg = _hand_made()
    assert PT.stage_ms(pg, "prefill", ("falcon.combine_a", "falcon.combine_h")) \
        == pytest.approx(150e-6)
    assert PT.stage_ms(pg, "prefill", ("falcon.lcma_gemm",)) == pytest.approx(300e-6)


def test_idle_is_attributed_by_the_engine_phases():
    pg = _hand_made()
    # sample 2750-3000 holds the slice 2760-2770: 240 idle
    assert PT.idle_ms(pg, "decode", ("sample",)) == pytest.approx(240e-6)
    # kv_gather 2050-2150 holds 2060-2100; kv_scatter 2650-2750 holds 2660-2700
    assert PT.idle_ms(pg, "decode", ("kv_gather", "kv_scatter")) \
        == pytest.approx((60 + 60) * 1e-6)
    assert PT.idle_ms(pg, "verify", ("sample",)) is None


def test_eager_programs_are_counted_per_decode_step():
    pg = _hand_made()
    assert PT.eager_ms(pg, "decode", "jit_decode_step") == pytest.approx(
        (40 + 40 + 10) * 1e-6)
    assert PT.eager_ms(pg, "prefill", "jit_chunk_step") == pytest.approx(20e-6)


def test_a_program_without_spans_or_scopes_reads_nothing():
    pg = _hand_made()
    bare = PT.Program([], pg.ops, pg.busy, pg.modules)
    assert PT.idle_ms(bare, "decode", ("sample",)) is None
    assert PT.eager_ms(bare, "decode", "jit_decode_step") is None
    assert PT.stage_split(bare, "prefill") is None
    unscoped = PT.Program(pg.spans, [[s, d, ""] for s, d, _ in pg.ops],
                          pg.busy, pg.modules)
    assert PT.stage_ms(unscoped, "prefill", ("falcon.lcma_gemm",)) is None
    assert PT.idle_ms(None, "decode", ("sample",)) is None
    assert PT.load({"cell": {"name": "no.such.cell"}}) is None


def _link(i, prev, shape="bf16[7,4,4]"):
    src = f"{shape} %bitcast_dynamic-update-slice_fusion.{prev}" if prev \
        else "bf16[8,8] %fusion.3"
    return (f"%bitcast_dynamic-update-slice_fusion.{i} = {shape} "
            f"fusion({src}, bf16[8,8] %fusion.3), kind=kLoop, calls=%f.{i}")


def test_an_in_place_chain_takes_the_scope_of_its_last_link():
    """Combine A's stack lowered as three in-place links, of which only the
    last keeps the op_name; the same names in another program stay as they
    are, and a chain that ends without a stage gains none."""
    names = {1: _link(1, None), 2: _link(2, 1), 3: _link(3, 2),
             4: _link(1, None), 5: _link(2, 1),
             6: "%fusion.9 = f32[7,4,4] fusion(bf16[7,4,4] "
                "%bitcast_dynamic-update-slice_fusion.3), kind=kOutput"}
    loop = "jit(chunk_step)/while"
    scopes = {1: (loop, 7), 2: (loop, 7),
              3: (f"{LOOP}/mlp/falcon.combine_a/concatenate", 7),
              4: (loop, 8), 5: (f"{loop}/body/concatenate", 8),
              6: (f"{LOOP}/mlp/falcon.lcma_gemm/dot_general", 7)}
    got = PT._chain_scopes(names, scopes)
    assert got[1] == got[2] == got[3] == scopes[3][0]
    assert got[4] == loop and got[5] == scopes[5][0]
    assert got[6] == scopes[6][0]


def test_device_events_read_the_scope_from_event_metadata(tmp_path):
    """A hand-written XSpace: the op's scope is the ``tf_op`` stat of its
    event metadata; times are the line's start plus the event's offset."""
    space = PT._xplane_classes()()
    pl = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "program_id")):
        e = pl.stat_metadata.add(key=key)
        e.value.id, e.value.name = key, name
    for key, name, scope in ((10, _link(1, None), "jit(f)/while:"),
                             (11, _link(2, 1), "jit(f)/while/body/falcon.combine_a/concatenate:"),
                             (12, "jit_f(42)", "")):
        e = pl.event_metadata.add(key=key)
        e.value.id, e.value.name = key, name
        if scope:
            e.value.stats.add(metadata_id=1, str_value=scope)
            e.value.stats.add(metadata_id=2, uint64_value=42)
    ops = pl.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=10, offset_ps=5000, duration_ps=2000)
    ops.events.add(metadata_id=11, offset_ps=8000, duration_ps=3000)
    mods = pl.lines.add(name="XLA Modules", timestamp_ns=1000)
    mods.events.add(metadata_id=12, offset_ps=4000, duration_ps=9000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    ops, modules = PT._device_events(PT._read(str(path)))
    scope = "jit(f)/while/body/falcon.combine_a/concatenate"
    assert ops == [(1005.0, 2.0, scope), (1008.0, 3.0, scope)]
    assert modules == [["jit_f(42)", 1004.0, 9.0]]


def test_generator_thread_spans_are_left_out(tmp_path):
    """Recorded on the CPU: the engine thread's spans are kept, and a
    second thread's spans, ``serve.*`` or not, are not mixed in."""
    import threading

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    def generate():
        with TraceAnnotation("bench.submit"):
            with TraceAnnotation("serve.other"):
                pass

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("serve.decode"):
            with TraceAnnotation("serve.sample"):
                t = threading.Thread(target=generate)
                t.start()
                t.join()
            with TraceAnnotation("serve.forward"):
                jnp.ones(4).block_until_ready()
    host = PT._engine_spans(PT._read(PT.TR.find_xplane(str(tmp_path))))
    assert [n for n, _, _ in host] == ["serve.decode", "serve.sample",
                                      "serve.forward"]


def test_recorded_piece_reads_as_on_the_chip():
    """A granite_3_2b.chat_prefill trace on a TPU v5e: one prefill step at
    the 1024 bucket (Strassen MLP) and the two decode steps after it.
    Combine A's seven in-place links all count (0.243 ms; the last link
    alone, which keeps the op_name, is 0.027 ms)."""
    pg = PT.load_json(DATA)
    kinds = [n for n, _, _ in pg.spans if n in ("serve.prefill", "serve.decode")]
    assert kinds == ["serve.prefill", "serve.decode", "serve.decode"]
    assert os.path.getsize(DATA) < 500_000
    combine = PT.stage_ms(pg, "prefill", ("falcon.combine_a", "falcon.combine_h"))
    lcma = PT.stage_ms(pg, "prefill", ("falcon.lcma_gemm",))
    assert combine == pytest.approx(0.8182245, rel=1e-6)
    assert PT.stage_ms(pg, "prefill", ("falcon.combine_a",)) == pytest.approx(
        0.2429231, rel=1e-6)
    assert lcma == pytest.approx(12.852807108, rel=1e-6)
    # the stages are part of the prefill program's device time
    (lo, hi), = PT._steps(pg, "prefill")
    chunk = 1e-6 * sum(d for n, s, d in pg.modules
                       if lo <= s < hi and PT.TR._base(n) == "jit_chunk_step")
    assert 0 < combine + lcma <= chunk
    # leaf self time adds up to the device time of the programs in the step
    eager = PT.eager_ms(pg, "prefill", "jit_chunk_step")
    assert sum(PT.stage_split(pg, "prefill").values()) == pytest.approx(
        chunk + eager, rel=1e-3)
    sample = PT.idle_ms(pg, "decode", ("sample",))
    kv = PT.idle_ms(pg, "decode", ("kv_gather", "kv_scatter"))
    assert sample == pytest.approx(3.3034508, rel=1e-6)
    assert kv == pytest.approx(6.5949668, rel=1e-6)
    steps = PT._steps(pg, "decode")
    host = 1e-6 * sum(b - a - PT._busy_in(pg.busy, a, b) for a, b in steps)
    assert sample + kv <= host / len(steps)
    assert PT.eager_ms(pg, "decode", "jit_decode_step") == pytest.approx(
        5.3503579, rel=1e-6)
