"""Device time on the first chip of the decode programs' (the engine's
``_tick_fn``) operations that sit under none of the step's named scopes,
divided by the decode steps of the traced window: the layer scan's slicing
of each layer's cache out of the stacked cache, its write-back, and the
copies XLA adds around them (``models/model.py`` ``decode_step``). The
steps are those ``decode_step_ms.tput`` divides by."""

from bench import scopes

PROGRAM = "_tick_fn"


def read(run):
    ops = scopes.program_ops(run.trace, scopes.of(run).hlo, run.devices[0], PROGRAM)
    steps = sum(len(s.decode) for s in run.steps)
    scoped = [scopes.in_scopes(sc, scopes.STEP_SCOPES) for _, sc in ops]
    if not steps or not any(scoped):  # a program without scopes tells nothing apart
        return None
    return 1e3 * sum(e.dur for (e, _), s in zip(ops, scoped) if not s) / steps
