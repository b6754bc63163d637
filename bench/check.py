"""The comparison that decides ``correct``: served tokens against the plain
float32 reference.

After the window, a sample of the requests the window finished (drawn
from the seed, always holding the longest) is run once through the
reference, prompt and served tokens together, layer by layer with weights
regenerated from the seed. For each served token the number read is how
far its reference logit lies below the reference's best at that position;
the widest such gap over the sample is compared with the cell's limit.
Greedy decoding in the served model's own precision keeps that gap small;
a token chosen by a lower precision, a wrong cache row or a dropped layer
does not.

The control (``control=True``, never in a benchmark run) puts the
reference in float8 e4m3 in the program's place and reads the same gap for
the token it ranks first at each position; the harness then holds that
gap, not the program's, against the limit.
"""

from __future__ import annotations

import importlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.harness import as_run
from bench.reference.common import QUERY_BLOCK

SAMPLE_TOKENS = 600  # served tokens the sample reaches, the longest included
SAMPLE_MAX = 6  # requests at most
ROW_BLOCK = 256  # logits rows per block


def choose(finished: list, seed: int) -> list:
    """The longest finished request, then others drawn from the seed until
    the sample holds SAMPLE_TOKENS served tokens or SAMPLE_MAX requests.
    ``finished`` holds (prompt, served) pairs."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -len(finished[i][1]))
    rest = list(np.random.default_rng(seed ^ 0x5EED).permutation(order[1:]))
    pick, total = [order[0]], len(finished[order[0]][1])
    while rest and total < SAMPLE_TOKENS and len(pick) < SAMPLE_MAX:
        i = int(rest.pop(0))
        pick.append(i)
        total += len(finished[i][1])
    return [finished[i] for i in pick]


def _pad(n: int) -> int:
    return -(-n // QUERY_BLOCK) * QUERY_BLOCK


def layer_weights(fam, hf: dict, seed: int, l: int, dtype: str) -> dict:
    """Layer ``l``'s weights for the reference family ``fam``, regenerated
    from the seed as served and upcast to f32. A family whose
    ``layer_leaves(hf, l)`` takes the layer maps each key to ``(path,
    stack index, shape, dtype)``, a dtype of None being the deployment's:
    its layers may differ in kind and sit in several stacks. The
    one-argument ``layer_leaves(hf)`` maps each key to ``(path, shape)``,
    the same for every layer, at stack index ``l`` in the deployment's
    dtype."""
    if len(inspect.signature(fam.layer_leaves).parameters) == 2:
        leaves = fam.layer_leaves(hf, l)
    else:
        leaves = {k: (path, l, shape, None) for k, (path, shape) in fam.layer_leaves(hf).items()}
    return {k: weights.layer_leaf(seed, path, i, shape, leaf_dtype or dtype)
            for k, (path, i, shape, leaf_dtype) in leaves.items()}


def gaps(conf: dict, seed: int, sample: list, control: bool = False) -> dict:
    """Run the reference over ``sample`` [(prompt, served)]; return the
    widest served-token gap and, with ``control``, the float8 control's."""
    hf = as_run(conf)
    fam = importlib.import_module(f"bench.reference.{conf['reference']}")
    dtype = conf["deployment"]["dtype"]
    precs = ["f32", "fp8"] if control else ["f32"]
    with jax.default_matmul_precision("highest"):
        layer = {p: jax.jit(lambda x, w, p=p: fam.layer(x, w, hf, p)) for p in precs}
        plain = {k: weights.plain_leaf(seed, path, shape, dtype)
                 for k, (path, shape) in fam.plain_leaves(hf).items()}
        seqs = []
        for prompt, served in sample:
            toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            seqs.append(np.pad(toks, (0, _pad(len(toks)) - len(toks))))
        xs = {p: [fam.embed(plain, jnp.asarray(s), hf) for s in seqs] for p in precs}
        for l in range(hf["num_hidden_layers"]):
            w = layer_weights(fam, hf, seed, l, dtype)
            for p in precs:
                xs[p] = [layer[p](x, w) for x in xs[p]]
            del w

        @jax.jit
        def read(plain, xf, x8, tok):
            ref = fam.logits(xf, plain, hf, "f32")
            best = jnp.max(ref, axis=-1)
            served_gap = best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
            if x8 is None:
                return served_gap, served_gap
            t8 = jnp.argmax(fam.logits(x8, plain, hf, "fp8"), axis=-1)
            return served_gap, best - jnp.take_along_axis(ref, t8[:, None], axis=-1)[:, 0]

        served_gaps, control_gaps = [], []
        for i, (prompt, served) in enumerate(sample):
            lo, n = len(prompt) - 1, len(served)
            for a in range(0, n, ROW_BLOCK):
                b = min(a + ROW_BLOCK, n)
                rows = np.arange(lo + a, lo + a + ROW_BLOCK)
                rows = np.minimum(rows, lo + n - 1)
                tok = np.zeros(ROW_BLOCK, np.int32)
                tok[: b - a] = served[a:b]
                xf = xs["f32"][i][rows]
                x8 = xs["fp8"][i][rows] if control else None
                g, c = read(plain, xf, x8, jnp.asarray(tok))
                served_gaps.append(np.asarray(g)[: b - a])
                control_gaps.append(np.asarray(c)[: b - a])
    g = np.concatenate(served_gaps) if served_gaps else np.zeros(0)
    out = {
        "max_gap": float(g.max()) if g.size else None,
        "tokens": int(g.size),
        "requests": len(sample),
        "differing": int((g > 0).sum()),
    }
    if control:
        c = np.concatenate(control_gaps)
        out["control_gap"] = float(c.max())
        out["control_differing"] = int((c > 0).sum())
    return out
