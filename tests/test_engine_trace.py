"""The engine's host spans and the programs' named scopes: what a profiler
trace of serving can attribute. Spans (``engine.*``) mark a scheduling
step's phases on the host; scopes (``jax.named_scope``) name the pieces of
one layer and one step in the compiled programs' op metadata."""

import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.models import LM
from repro.serve import Request, SamplingParams, ServeEngine

SCOPES = ("attn_in", "kv_write", "attention", "attn_out", "mlp", "head", "sample")
# the spans a plain (no speculation) step takes when it admits a short and
# a long prompt: a fused admission, a pack, a decode chunk, one harvest
SPANS = ("engine.step", "engine.admit", "engine.pack", "engine.chunk",
         "engine.harvest", "engine.wait")


@pytest.fixture(scope="module", params=["codeqwen1.5-7b", "minicpm3-4b"])
def model(request):
    cfg = get_arch(request.param).reduced()  # GQA; MLA latents
    m = LM(cfg)
    return cfg, m, m.init(jax.random.key(0))


def _engine(m, p):
    return ServeEngine(m, p, batch_slots=2, max_len=64, prefill_budget=8)


def _submit(eng, cfg):
    rng = np.random.default_rng(3)
    # a short prompt admits fused and decodes at once; a long one is packed
    for rid, n in ((0, 5), (1, 20)):
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        eng.submit(Request(rid=rid, prompt=prompt, params=SamplingParams(max_new=6)))


def _drive(eng) -> dict:
    while eng.step():
        pass
    return {r.rid: list(r.generated) for r in eng.finished}


def _profile(directory):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return jax.profiler.trace(str(directory), profiler_options=opts)


def _events(directory):
    """(name, start_ns, end_ns, stats) of every host-plane event."""
    path = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def test_programs_carry_every_scope(model):
    cfg, m, p = model
    eng = _engine(m, p)
    for name, lowered in eng.lower_dispatch().items():
        text = lowered.compile().as_text()
        parts = {c for path in re.findall(r'op_name="([^"]*)"', text)
                 for c in path.split("/")}
        missing = [s for s in SCOPES if s not in parts]
        assert not missing, (cfg.name, name, missing)


def test_profiler_leaves_tokens_unchanged(model, tmp_path):
    cfg, m, p = model
    plain = _engine(m, p)
    _submit(plain, cfg)
    want = _drive(plain)
    traced = _engine(m, p)
    _submit(traced, cfg)
    with _profile(tmp_path):
        got = _drive(traced)
    assert got == want and len(got) == 2


def test_one_profile_holds_the_spans_and_the_tick(model, tmp_path):
    cfg, m, p = model
    eng = _engine(m, p)
    eng.prewarm()  # nothing compiles inside the profile
    _submit(eng, cfg)
    with _profile(tmp_path):
        _drive(eng)
    evs = _events(tmp_path)
    spans = [e for e in evs if e[0].startswith("engine.")]
    first = [e for e in spans if e[0] == "engine.step"]
    # the first step admits both, packs the long prompt, decodes the short
    # one, and harvests the admission's first token
    s0, e0 = first[0][1], first[0][2]
    in_first = {n for n, a, b, _ in spans if s0 <= a and b <= e0}
    assert set(SPANS) <= in_first, in_first
    # the CPU backend's operations of the decode ticks lie on the same clock,
    # inside the steps that dispatched and harvested them
    tick = [e for e in evs if "_tick_fn" in str(e[3].get("hlo_module", ""))]
    assert tick
    assert all(s0 <= a and b <= first[-1][2] for _, a, b, _ in tick)
