"""Scopes and engine spans (``bench/scopes.py``) and the per-layer readers
that read them, on small traces written out by hand (``test_trace.py``'s
``xspace``), against counts worked out by hand."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness, scopes, trace
from bench.flops import attention, mla
from bench.tests import tiny
from bench.tests.test_trace import CODEQWEN, NS, NS_PEAK, _metric, _run, xspace

MINICPM = harness.as_run(harness.load_json(harness.HERE / "configs" / "minicpm3-4b.json"))
TICK = "jit(_tick_fn)/while/body/while/body/"  # a scanned layer inside a decode chunk


def _given(monkeypatch, device_ops, modules, spans=()):
    """Let the readers find, beyond the hand-written trace, every module's
    HLO naming each op's scope (an op's fifth field) and the ``engine.*``
    spans among ``spans``."""
    named = {op[0]: op[4] for op in device_ops if len(op) > 4}
    extra = scopes.Extra({m[0]: named for m in modules},
                         [trace.Event(n, s * 1e-9, d * 1e-9) for n, s, d in spans
                          if n.startswith(scopes.ENGINE_PREFIX)])
    monkeypatch.setattr(scopes, "of", lambda run: extra)


def test_scan_time_is_what_no_step_scope_holds(monkeypatch):
    ops = [("while.1", 0, 90 * NS, "m", "jit(_tick_fn)/while"),  # a loop: its body counts
           ("dynamic-slice_bitcast_fusion.1", 1 * NS, 3 * NS, "m", TICK.rstrip("/")),
           ("fusion.2", 4 * NS, 5 * NS, "m", TICK + "attn_in/dot_general"),
           ("fusion.3", 9 * NS, 10 * NS, "m", TICK + "attention/dot_general"),
           ("copy.4", 19 * NS, 2 * NS, "m"),  # no scope at all
           ("fusion.5", 21 * NS, 4 * NS, "m", TICK + "decode_attention/mul"),  # not `attention`
           ("fusion.6", 25 * NS, 6 * NS, "m", TICK + "mlp/add"),
           ("fusion.7", 31 * NS, 1 * NS, "m", "jit(_tick_fn)/while/body/sample/argmax"),
           ("copy.8", 95 * NS, 7 * NS, "m", "jit(_packed_fn)")]  # another program
    mods = [("jit__tick_fn(7)", 0, 92 * NS), ("jit__packed_fn(2)", 94 * NS, 9 * NS)]
    tr = xspace(ops, mods, [("bench.window", 0, 120 * NS)])
    _given(monkeypatch, ops, mods)
    steps = [harness.StepRecord(t=0.0, waiting=0, busy=2, decode=[[10, 20], [11, 21]])]
    run = _run(tr, steps, MINICPM)
    # 3 + 2 + 4 ms of the tick under no step scope, over two decode steps
    assert _metric("decode_scan_ms.tput", run) == pytest.approx(4.5)
    first, scope = scopes.program_ops(tr, scopes.of(run).hlo, 0, "_tick_fn")[0]
    assert (first.name, scope) == ("dynamic-slice_bitcast_fusion.1", TICK.rstrip("/"))
    assert trace.top_ops(tr, 0)[0] == ["jit__tick_fn:fusion", pytest.approx(0.026)]


def test_mla_roofline_reads_exactly_100_when_time_is_the_least(monkeypatch):
    hf = MINICPM
    decode = [[1000, 300, 2048], [1001, 301, 2049]]  # two steps of three slots
    L = hf["num_hidden_layers"]
    ops, t = [], 0
    for c in decode:
        least = attention.least_seconds(*mla.mla_decode_call(hf, c), NS_PEAK) * 1e9
        for _ in range(L):  # one call per layer, and a write outside the scope
            ops.append(("fusion.1", t, least, "m", TICK + "attention/dot_general"))
            ops.append(("fusion.2", t + least, 1000, "m", TICK + "kv_write/scatter"))
            t += least + 1000
    mods = [("jit__tick_fn(3)", 0, t)]
    steps = [harness.StepRecord(t=0.0, waiting=0, busy=3, decode=decode)]
    tr = xspace(ops, mods, [("bench.window", 0, t + 10)])
    _given(monkeypatch, ops, mods)
    run = _run(tr, steps, hf, peak=NS_PEAK)
    assert _metric("mla_attn_roofline.tput", run) == pytest.approx(100.0, rel=1e-12)
    tr2 = xspace([(n, 2 * s, 2 * d, m, sc) for n, s, d, m, sc in ops],
                 [("jit__tick_fn(3)", 0, 2 * t)], [("bench.window", 0, 2 * t + 10)])
    run2 = _run(tr2, steps, hf, peak=NS_PEAK)
    assert _metric("mla_attn_roofline.tput", run2) == pytest.approx(50.0, rel=1e-12)
    # the GQA cell has no latent attention to read
    assert _metric("mla_attn_roofline.tput", _run(tr, steps, CODEQWEN, peak=NS_PEAK)) is None


def test_host_busy_is_step_time_less_waiting(monkeypatch):
    spans = [("bench.window", 0, 100 * NS),
             ("engine.step", 0, 10 * NS), ("engine.wait", 6 * NS, 3 * NS),
             ("engine.step", 20 * NS, 4 * NS),
             ("engine.wait", 30 * NS, 1 * NS),  # outside any step: not counted
             ("engine.step", 95 * NS, 10 * NS)]  # past the window's end
    tr = xspace([("fusion", 0, NS, "m")], [], spans)
    _given(monkeypatch, [], [], spans)
    assert _metric("host_busy_ms.tput", _run(tr, [])) == pytest.approx((7 + 4) / 2)


def test_new_readers_find_nothing_in_a_program_without_spans_or_scopes(monkeypatch):
    # what the program read before it had spans and scopes: ops with a
    # name stack but no step scope, and only the benchmark's own spans
    ops = [("fusion.1", 0, 5 * NS, "m", "jit(_tick_fn)/while/body/dot_general"),
           ("copy.2", 5 * NS, 2 * NS, "m")]
    mods = [("jit__tick_fn(1)", 0, 8 * NS)]
    spans = [("bench.window", 0, 10 * NS), ("bench.step", 0, 9 * NS)]
    tr = xspace(ops, mods, spans)
    _given(monkeypatch, ops, mods, spans)
    steps = [harness.StepRecord(t=0.0, waiting=0, busy=1, decode=[[5], [6]])]
    run = _run(tr, steps, MINICPM)
    for name in ("decode_scan_ms.tput", "mla_attn_roofline.tput", "host_busy_ms.tput"):
        assert _metric(name, run) is None, name


def test_readers_find_nothing_where_no_profile_is(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    tr = xspace([("fusion.1", 0, NS, "m")], [("jit__tick_fn(1)", 0, 2 * NS)],
                [("bench.window", 0, 3 * NS)])
    steps = [harness.StepRecord(t=0.0, waiting=0, busy=1, decode=[[5]])]
    assert scopes.of(None) == scopes.Extra()
    for name in ("decode_scan_ms.tput", "mla_attn_roofline.tput", "host_busy_ms.tput"):
        assert _metric(name, _run(tr, steps, MINICPM)) is None, name


def test_scope_and_spans_from_a_profile(tmp_path):
    """An op's scope is the ``op_name`` of its instruction in the HLO of
    the program it ran in, which the profile keeps; the engine's spans are
    read beside it."""

    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            return jnp.sin(x) @ x

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    with trace.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation("engine.step"):
            f(x).block_until_ready()
    extra = scopes.load(str(tmp_path))
    assert [s.name for s in extra.spans] == ["engine.step"]
    path = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))[0]
    with open(path, "rb") as fh:
        assert scopes.hlo_scopes(fh.read()) == extra.hlo
    prog = next(k for k in extra.hlo if k.startswith("jit_f("))
    dot = next(n for n, s in extra.hlo[prog].items() if s.endswith("/attention/dot_general"))
    ops = [(dot, NS, 2 * NS, "m"), ("fusion.9", 3 * NS, NS, "m")]  # not in the HLO
    tr = xspace(ops, [(prog, 0, 5 * NS)], [("bench.window", 0, 10 * NS)])
    got = scopes.program_ops(tr, extra.hlo, 0, "jit_f")
    assert [sc for _, sc in got] == [extra.hlo[prog][dot], ""]
    assert scopes.in_scopes(got[0][1], ("attention",))


def test_mla_decode_call_counts_latent_rows_once():
    hf = tiny.MLA  # H 4, kv_lora 16, rope 8
    f, b = mla.mla_decode_call(hf, [5, 7])
    # scores over latent + rope, readout over latent, at 12 keys
    assert f == 2 * 4 * 24 * 12 + 2 * 4 * 16 * 12
    # absorbed + rope query in, latent readout out, each key's row once
    assert b == 2 * (2 * 4 * 24 + 2 * 4 * 16 + 12 * 24)
