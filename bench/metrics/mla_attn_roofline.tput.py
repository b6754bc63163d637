"""The MLA decode attention's share of its roofline: least time of the
latent attention the traced window's decode steps made, over the device
time on the first chip of the decode programs' (``_tick_fn``) operations
under the scope ``attention`` (``models/mla.py`` ``mla_decode``: from the
absorbed query against the latent cache through the latent readout). Each
step attends once per layer over the active slots."""

from bench import scopes
from bench.flops import attention, mla

PROGRAM = "_tick_fn"
SCOPE = ("attention",)


def read(run):
    if "kv_lora_rank" not in run.hf:
        return None
    ops = scopes.program_ops(run.trace, scopes.of(run).hlo, run.devices[0], PROGRAM)
    secs = sum(e.dur for e, sc in ops if scopes.in_scopes(sc, SCOPE))
    least = 0.0
    for s in run.steps:
        for contexts in s.decode:
            f, b = mla.mla_decode_call(run.hf, contexts)
            least += run.hf["num_hidden_layers"] * attention.least_seconds(
                f / run.chips, b / run.chips, run.peak)
    if secs <= 0 or least <= 0:
        return None
    return 100.0 * least / secs
