"""Chip smoke test: serve codeqwen1.5-7b at its published widths on a TPU.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the split/merge cluster on four chips

One chip: the model is codeqwen1.5-7b in bf16 at its published widths
(d_model 4096, 32 query heads over 4 KV heads of 128, d_ff 13440, vocab
92416), cut to 16 of its 32 layers — one stage of a two-stage pipeline,
4.00B parameters (8.0 GB); the whole model (14.5 GB) cannot sit on one
16 GB chip beside a cache. Weights are random from ``--seed``. The script

1. checks every attention kernel of the serving path (decode, ragged,
   paged, gqa flash) against its ``kernels/ref.py`` oracle at the served
   shapes;
2. serves 16 requests (prompts of 32-2048 tokens, 32 new tokens each)
   through ``ServeEngine`` exactly as ``python -m repro.launch.serve``
   does — build, construct, ``prewarm()``, ``submit``, ``run()`` — first
   with the dense cache, then with the paged pool (``kv_block_size=16``),
   each with 16 slots of 4096 positions (2.1 GB of bf16 KV);
3. holds the dense and paged prefill logits of one prompt to a bf16
   tolerance.

Four chips (``--chips 4``) runs only the cluster path and what it is
compared with: a one-chip engine on device 0, the split fabric (four
one-chip replicas behind the router) and the merge fabric (tensor
parallel over four chips) on the 16-layer cut, then merge serving the
whole 32-layer model.

Everything runs in this one process, which owns the chip(s). Any failed
check raises, so the exit code is non-zero and the result line is never
printed. The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Tolerances, each with its reason.
# Kernel vs oracle: the kernels read bf16 K/V and write bf16 outputs, so
# their result carries one bf16 rounding (2^-9 relative) plus the bf16
# rounding of the probabilities fed to the MXU for the value readout
# (another 2^-9 per term); the oracle runs in f32 at "highest" precision on
# the same bf16 inputs. 1e-2 relative L2 error is ~4x that budget, while a
# wrong mask, slot, head or block mapping moves the output by O(1) of its
# own scale (the queries are scaled up so attention is peaked, not flat).
KERNEL_REL_L2 = 1e-2
# Logits of one prompt from two bf16 serving paths (dense vs paged kernels,
# or four-chip merge vs one chip): each layer rounds its bf16 outputs
# (2^-8 relative) at points where the two paths order their f32 sums
# differently; over 16 layers those independent roundings add as a random
# walk to ~1-2% of the logits' L2 norm. 5e-2 leaves that a 2.5-5x margin;
# a path that drops a tile, a head or a shard is off by O(1).
LOGITS_REL_L2 = 5e-2


@dataclasses.dataclass(frozen=True)
class Size:
    """What the smoke runs at; ``FULL`` on the chip."""

    n_layers: int = 16
    batch_slots: int = 16
    max_len: int = 4096
    n_requests: int = 16
    min_prompt: int = 32
    max_prompt: int = 2048
    max_new: int = 32
    kv_block_size: int = 16
    pack: int = 64  # packed tokens per kernel check (the widest T bucket)
    probe: int = 256  # prompt length of the logits comparisons


FULL = Size()
# the four-chip comparisons serve a lighter stream: four chips cost four
# times as much a second, and the cluster paths need no longer prompts to
# take every dispatch (fused admission, ragged packs, decode ticks)
FOUR = dataclasses.replace(FULL, n_requests=8, max_prompt=1024, max_new=16)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def check(name: str, err: float, tol: float) -> None:
    verdict = "ok" if err <= tol else "FAILED"
    log(f"  {name}: rel L2 error {err:.3e} (tolerance {tol:.0e}) {verdict}")
    if err > tol:
        raise AssertionError(f"{name}: rel L2 error {err:.3e} > {tol:.0e}")


class CompileMeter:
    """Counts XLA backend compiles and their seconds while installed."""

    def __init__(self) -> None:
        import jax

        self.n, self.seconds = 0, 0.0
        self._event = "/jax/core/compile/backend_compile_duration"
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.n += 1
            self.seconds += duration

    def mark(self) -> tuple[int, float]:
        return self.n, self.seconds


# ---------------------------------------------------------------- workload


def workload(size: Size, vocab: int, seed: int) -> list[np.ndarray]:
    """Seeded prompts whose lengths spread geometrically over
    [min_prompt, max_prompt]: the shortest take the fused admission
    (B=1 prefill, <= prefill budget), the rest the chunked ragged packs."""
    rng = np.random.default_rng(seed)
    lens = np.geomspace(size.min_prompt, size.max_prompt, size.n_requests)
    return [
        rng.integers(0, vocab, size=int(n)).astype(np.int32)
        for n in rng.permutation(np.round(lens))
    ]


def serve(target, prompts, max_new: int) -> tuple[dict, float]:
    """Submit every prompt, run to completion; {rid: tokens}, seconds."""
    from repro.serve import Request, SamplingParams

    for i, pr in enumerate(prompts):
        target.submit(Request(rid=i, prompt=pr, params=SamplingParams(max_new=max_new)))
    t0 = time.perf_counter()
    target.run()
    secs = time.perf_counter() - t0
    done = {r.rid: list(r.generated) for r in target.finished}
    if sorted(done) != list(range(len(prompts))):
        raise AssertionError(f"finished {sorted(done)} of {len(prompts)} requests")
    short = {rid: len(t) for rid, t in done.items() if len(t) != max_new}
    if short:
        raise AssertionError(f"requests ended short of {max_new} tokens: {short}")
    return done, secs


def release() -> None:
    """Free the device buffers of engines just deleted: an engine holds
    reference cycles (its jitted bound methods), so ``del`` alone leaves its
    KV cache on the chip until the collector runs — possibly after the next
    engine has allocated its own."""
    gc.collect()


def agreement(a: dict, b: dict) -> float:
    """Share of greedy tokens that agree position by position."""
    same = sum(x == y for rid in a for x, y in zip(a[rid], b[rid]))
    return same / max(sum(len(t) for t in a.values()), 1)


def require_kernels(engine) -> None:
    """The compiled tick and packed programs must carry the Pallas
    attention kernels: no silent oracle fallback, no interpret mode."""
    for name, lowered in engine.lower_dispatch().items():
        has = "tpu_custom_call" in lowered.compile().as_text()
        log(f"  {name} program contains tpu_custom_call: {has}")
        if not has:
            raise AssertionError(f"the {name} program has no Pallas kernel")


# ------------------------------------------------------------------ phases


def check_kernels(cfg, size: Size, seed: int) -> None:
    """Each attention kernel of the serving path vs its oracle, at the
    served shapes, oracles in f32 at "highest" matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, s, t, bs = size.batch_slots, size.max_len, size.pack, size.kv_block_size
    keys = iter(jax.random.split(jax.random.key(seed + 1), 16))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def rand(shape, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape, f32)).astype(bf16)

    def up(*xs):
        return [x.astype(f32) for x in xs]

    k, v = rand((b, kv, s, hd)), rand((b, kv, s, hd))
    qd, qt = rand((b, h, hd), 3.0), rand((t, h, hd), 3.0)
    cur = jax.random.randint(next(keys), (b,), 0, s)
    slot = jax.random.randint(next(keys), (t,), 0, b)
    pos = jax.random.randint(next(keys), (t,), 0, s)
    nb = b * s // bs
    pool_k, pool_v = rand((nb, kv, bs, hd)), rand((nb, kv, bs, hd))
    btab = jax.random.permutation(next(keys), nb).reshape(b, s // bs).astype(jnp.int32)
    g_q, g_k, g_v = rand((1, h, size.pack, hd), 3.0), rand((1, kv, size.pack, hd)), rand(
        (1, kv, size.pack, hd)
    )

    dec, rag = (qd, k, v, cur), (qt, k, v, slot, pos)
    pdec, prag = (qd, pool_k, pool_v, cur, btab), (qt, pool_k, pool_v, slot, pos, btab)
    # name: (kernel call, oracle call, operands); operands go in as jit
    # arguments, never as closed-over constants
    cases = {
        "decode_attention": (
            ops.decode_attention,
            lambda q, k, v, c: ops.decode_attention(*up(q, k, v), c, mode="ref"),
            dec,
        ),
        "ragged_attention": (
            ops.ragged_attention,
            lambda q, k, v, sl, po: ops.ragged_attention(*up(q, k, v), sl, po, mode="ref"),
            rag,
        ),
        "paged_ragged_attention": (
            ops.paged_ragged_attention,
            lambda q, k, v, sl, po, bt: ops.paged_ragged_attention(
                *up(q, k, v), sl, po, bt, mode="ref"
            ),
            prag,
        ),
        "paged_decode_attention": (
            ops.paged_decode_attention,
            lambda q, k, v, c, bt: ops.paged_decode_attention(
                *up(q, k, v), c, bt, mode="ref"
            ),
            pdec,
        ),
        "gqa_flash_attention": (
            ops.gqa_flash_attention,
            lambda q, k, v: ref.gqa_flash_attention(
                *up(q.reshape(kv, h // kv, size.pack, hd), k[0], v[0])
            ).reshape(1, h, size.pack, hd),
            (g_q, g_k, g_v),
        ),
    }
    for name, (kernel, oracle, args) in cases.items():
        got = jax.jit(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        check(name, rel_l2(got, want), KERNEL_REL_L2)


def prefill_logits(model, params, prompt, *, paged_block: int = 0):
    """Logits of every prompt position, fed through ``packed_step`` in
    64-token chunks against a one-slot dense cache or a paged pool with an
    identity block table — the same kernels the engines run."""
    import jax
    import jax.numpy as jnp

    s_max = -(-len(prompt) // 64) * 64
    if paged_block:
        cache = model.init_kv_pool(s_max // paged_block, paged_block)
        btab = jnp.arange(s_max // paged_block, dtype=jnp.int32)[None]
    else:
        cache, btab = model.init_cache(1, s_max), None
    step = jax.jit(model.packed_step, donate_argnums=(1,))
    out = []
    for st in range(0, len(prompt), 64):
        chunk = jnp.asarray(prompt[st : st + 64])
        logits, cache = step(
            params, cache, chunk, jnp.zeros(len(chunk), jnp.int32),
            jnp.arange(st, st + len(chunk), dtype=jnp.int32), block_tables=btab,
        )
        out.append(np.asarray(logits.astype(jnp.float32)))
    return np.concatenate(out)


def run_one_chip(cfg, size: Size, seed: int) -> int:
    """The one-chip phases on ``cfg``; returns tokens served."""
    import jax

    from repro.models import LM
    from repro.serve import ServeEngine

    meter = CompileMeter()
    model = LM(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init_params(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(
        f"model {cfg.name} {cfg.dtype}: d_model {cfg.d_model}, {cfg.n_heads} heads over "
        f"{cfg.n_kv_heads} KV heads of {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}; cut to {cfg.n_layers} layers"
    )
    log(
        f"params {n_params / 1e9:.3f}B, {sum(x.nbytes for x in jax.tree.leaves(params)):,} "
        f"bytes, built in {time.perf_counter() - t0:.1f} s"
    )

    log("[kernels vs oracles at the served shapes]")
    check_kernels(cfg, size, seed)

    prompts = workload(size, cfg.vocab_size, seed)
    log(
        f"workload: {len(prompts)} requests, prompt lengths "
        f"{sorted(len(p) for p in prompts)}, {size.max_new} new tokens each"
    )
    results, served = {}, 0
    for label, block in (("dense", None), ("paged", size.kv_block_size)):
        log(f"[{label} unified engine]")
        eng = ServeEngine(
            model, params, batch_slots=size.batch_slots, max_len=size.max_len,
            seed=seed, kv_block_size=block,
        )
        kv_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
        n0, s0 = meter.mark()
        t0 = time.perf_counter()
        eng.prewarm()
        n1, s1 = meter.mark()
        log(
            f"  KV {kv_bytes:,} bytes; prewarm {time.perf_counter() - t0:.1f} s, "
            f"{n1 - n0} programs compiled in {s1 - s0:.1f} s"
        )
        require_kernels(eng)
        results[label], secs = serve(eng, prompts, size.max_new)
        toks = sum(len(t) for t in results[label].values())
        served += toks
        log(f"  served {len(prompts)} requests, {toks} tokens in {secs:.2f} s wall (chip run)")
        del eng
        release()
    log(
        "greedy tokens agreeing dense vs paged: "
        f"{agreement(results['dense'], results['paged']):.4f}"
    )

    log("[prefill logits, dense vs paged]")
    probe = workload(dataclasses.replace(size, n_requests=1, min_prompt=size.probe,
                                         max_prompt=size.probe), cfg.vocab_size, seed + 2)[0]
    dense = prefill_logits(model, params, probe)
    paged = prefill_logits(model, params, probe, paged_block=size.kv_block_size)
    check("paged vs dense logits", rel_l2(paged, dense), LOGITS_REL_L2)
    log(f"  argmax agreement {np.mean(paged.argmax(-1) == dense.argmax(-1)):.4f}")
    return served


def run_four_chips(cfg, cfg_whole, size: Size, seed: int) -> int:
    """Split and merge on four chips vs one chip on ``cfg``, then merge
    serving ``cfg_whole``; returns tokens served."""
    import jax

    from repro.dist.sharding import param_shardings, serving_mesh_info
    from repro.models import LM
    from repro.serve import ServeCluster, ServeEngine
    from repro.serve.backend import DeviceBackend

    devs = jax.devices()[:4]
    if len(devs) != 4:
        raise AssertionError(f"--chips 4 needs four devices, found {len(devs)}")
    meter = CompileMeter()
    engine_kw = dict(batch_slots=size.batch_slots, max_len=size.max_len, seed=seed)
    model = LM(cfg)
    params = jax.block_until_ready(model.init_params(seed))  # on device 0
    prompts = workload(size, cfg.vocab_size, seed)
    probe = prompts[int(np.argmax([len(p) for p in prompts]))][: size.probe]
    log(
        f"model {cfg.name} {cfg.dtype} cut to {cfg.n_layers} layers; "
        f"{len(prompts)} requests, prompt lengths {sorted(len(p) for p in prompts)}"
    )

    def prefill(m, p):
        logits, _ = jax.jit(m.prefill, static_argnums=2)(p, {"tokens": probe[None]}, len(probe))
        return np.asarray(logits[0].astype(np.float32))

    def timed(label, target):
        n0, s0 = meter.mark()
        done, secs = serve(target, prompts, size.max_new)
        n1, s1 = meter.mark()
        toks = sum(len(t) for t in done.values())
        log(
            f"  {label}: {toks} tokens in {secs:.2f} s wall (chip run), "
            f"{n1 - n0} programs compiled in {s1 - s0:.1f} s of it"
        )
        return done, toks

    log("[one chip: device 0]")
    one = ServeEngine(model, params, backend=DeviceBackend(devs[0]), **engine_kw)
    ref_toks, served = timed("one chip", one)
    del one
    release()
    ref_logits = prefill(model, params)

    log("[split: four one-chip replicas behind the router]")
    split = ServeCluster(model, params, mode="split", devices=devs, **engine_kw)
    homes = {e.backend.device.id for e in split.engines}
    log(f"  replicas on devices {sorted(homes)}")
    if len(homes) != 4:
        raise AssertionError(f"split replicas share devices: {sorted(homes)}")
    split_toks, n = timed("split", split)
    served += n
    same = agreement(split_toks, ref_toks)
    log(f"  greedy tokens identical to one chip: {same:.4f}")
    if same != 1.0:
        raise AssertionError("split replicas disagree with the one-chip engine")
    del split
    release()

    log("[merge: tensor parallel over four chips]")
    merge = ServeCluster(model, params, mode="merge", devices=devs, **engine_kw)
    require_kernels(merge.engines[0])
    merge_toks, n = timed("merge", merge)
    served += n
    log(f"  greedy tokens agreeing with one chip: {agreement(merge_toks, ref_toks):.4f}")
    tp = merge.engines[0]
    check("merge vs one-chip prefill logits", rel_l2(prefill(tp.model, tp.params), ref_logits),
          LOGITS_REL_L2)
    del merge, tp, params
    release()

    log(f"[merge: the whole {cfg_whole.n_layers}-layer model over four chips]")
    cfg32 = cfg_whole
    model32 = LM(cfg32)
    info = serving_mesh_info(devs)
    t0 = time.perf_counter()
    params32 = jax.block_until_ready(model32.init_params(
        seed, out_shardings=param_shardings(model32.param_specs(), info)
    ))
    per_dev = {d.id: 0 for d in devs}
    for leaf in jax.tree.leaves(params32):
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    log(
        f"  {cfg32.n_layers} layers, {sum(x.nbytes for x in jax.tree.leaves(params32)):,} "
        f"param bytes built sharded in {time.perf_counter() - t0:.1f} s; "
        f"per device {per_dev}"
    )
    whole = ServeCluster(model32, params32, mode="merge", devices=devs, **engine_kw)
    _, n = timed(f"merge, {cfg_whole.n_layers} layers", whole)
    served += n
    return served


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke.py: run it from a checkout (src/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 3

    from repro.common.utils import enable_compile_cache
    from repro.configs import get_arch
    from repro.kernels import autotune

    cache_dir = enable_compile_cache()
    # block sizes come from the tuner's defaults, never from a file that
    # git does not track
    autotune.set_tuner(autotune.Autotuner(path=os.devnull, sweep=False))
    log(
        f"device {dev.platform} {dev.device_kind} x{len(devices)}; jax {jax.__version__}; "
        f"compile cache {cache_dir}"
    )
    whole = get_arch("codeqwen1.5-7b")
    cut = dataclasses.replace(whole, n_layers=FULL.n_layers)
    t0 = time.perf_counter()
    if args.chips == 4:
        served = run_four_chips(cut, whole, FOUR, args.seed)
    else:
        served = run_one_chip(cut, FULL, args.seed)
    stats = [d.memory_stats() or {} for d in devices[: args.chips]]
    log(f"peak_bytes_in_use per device: {[s.get('peak_bytes_in_use') for s in stats]}")
    log(f"tokens served {served}; wall {time.perf_counter() - t0:.1f} s (chip run)")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
