"""What ``mla_attn_roofline.tput`` reads: the MLA decode's latent attention
in the compiled decode tick sits under the scope ``attention``, as the
profile's HLO names it and ``bench/scopes.py`` reads it. Its score
einsums against the latent and rope caches and its latent readout carry
the scope; the cache write beside them does not."""

import jax
import numpy as np

from bench import harness, scopes, trace, weights
from bench.tests import tiny


def _tick_scopes(tmp_path) -> list[str]:
    """The ``op_name`` of every instruction of the decode tick's HLO, from
    a profile of a tiny MLA engine serving two requests."""
    from repro.models import LM
    from repro.serve import Request, SamplingParams, ServeEngine

    cell = tiny.cell("mla")
    model = LM(harness.arch_config(cell.conf))
    eng = ServeEngine(model, weights.build(model.param_specs(), 3), batch_slots=2, max_len=64)
    rng = np.random.default_rng(0)
    for rid in range(2):
        prompt = rng.integers(0, 512, 6).astype(np.int32)
        eng.submit(Request(rid=rid, prompt=prompt, params=SamplingParams(max_new=24)))
    eng.step()  # admits and compiles
    with trace.capture(str(tmp_path)):
        for _ in range(3):
            eng.step()
        jax.block_until_ready(eng.cache)
    hlo = scopes.load(str(tmp_path)).hlo
    ticks = [name for name in hlo if name.startswith("jit__tick_fn(")]
    assert ticks, sorted(hlo)
    return [op for name in ticks for op in hlo[name].values()]


# ``mla_decode``'s einsums: scores of the absorbed query against the
# latent rows and of the rope query against the rope rows, and the latent
# readout; each names its spec in the op_name
EINSUMS = ("bshr,btr->bhst", "bshk,btk->bhst", "bhst,btr->bshr")


def test_mla_decode_einsums_carry_the_attention_scope(tmp_path):
    ops = _tick_scopes(tmp_path)
    attention = [op for op in ops if scopes.in_scopes(op, ("attention",))]
    for spec in EINSUMS:
        assert any(f"/attention/{spec}/dot_general" in op for op in attention), spec
    # the layer's rows are read out of the carried stacked cache in the scope
    assert any(op.endswith("/attention/dynamic_slice") for op in attention), attention
    written = [op for op in ops if scopes.in_scopes(op, ("kv_write",))]
    assert written and not any(scopes.in_scopes(op, ("attention",)) for op in written)
